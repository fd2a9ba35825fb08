#!/usr/bin/env python3
"""``server_child.py`` with one fault planted in the timed path, chosen
by ``BENCH_FAULT``: tests only (``test_rehearsal.py`` sees ``correct``
come out false for each).

- ``alter_answer``: every bid's price is one unit higher where the
  window program produces it, so every maximum the view holds is;
- ``drop_half``: half of every chunk is left out (marked not valid);
- ``skip_step``: every other window program is not run, its rows are
  counted all the same (a step that returns its state unchanged);
- ``skip_write``: every other checkpoint commit writes nothing, and the
  uploader acknowledges it all the same.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import server_child  # noqa: E402


def plant(fault: str) -> None:
    import jax.numpy as jnp

    import risingwave_tpu  # noqa: F401
    from risingwave_tpu.connector import nexmark
    from risingwave_tpu.stream import runtime

    if fault == "alter_answer":
        plain = nexmark._next_price
        nexmark._next_price = lambda eid, stream: plain(eid, stream) + 1
    elif fault == "drop_half":
        plain_bids = nexmark.NexmarkGenerator._bids_impl

        def half(self, k0, cap):
            ch = plain_bids(self, k0, cap)
            return ch.__class__(ch.columns, ch.ops,
                                jnp.arange(cap) % 2 == 0, ch.schema)

        nexmark.NexmarkGenerator._bids_impl = half
    elif fault == "skip_step":
        plain_run = runtime.StreamingJob.run_chunks
        calls = {"n": 0}

        def every_other(self, n):
            calls["n"] += 1
            if n > 1 and calls["n"] % 2 == 0:
                self.source.offset += self.source.cap * n
                return self.source.cap * n
            return plain_run(self, n)

        runtime.StreamingJob.run_chunks = every_other
    elif fault == "skip_write":
        from risingwave_tpu.storage import checkpoint_store

        plain_commit = checkpoint_store.CheckpointStore.commit
        commits = {"n": 0}

        def every_other_commit(self, prep):
            commits["n"] += 1
            if commits["n"] % 2:
                plain_commit(self, prep)

        checkpoint_store.CheckpointStore.commit = every_other_commit
    else:
        raise SystemExit(f"unknown BENCH_FAULT {fault!r}")


if __name__ == "__main__":
    plant(os.environ["BENCH_FAULT"])
    server_child.main()
