"""Native Nexmark event generator, vectorized and device-resident.

Reference counterpart: ``src/connector/src/source/nexmark/`` (the
reference wraps the `nexmark` crate's sequential generator; proportions
and id chaining follow the canonical Beam/Flink NEXMark generator).

TPU-first design
----------------
The canonical generator is a sequential RNG walk.  Here every random
field is derived from a *counter-based* hash of the global event number
(splitmix64 mix), so generation is a pure vectorized function of an
index vector — a whole chunk of events materializes as one fused XLA
program directly on device, and any split/offset is addressable O(1)
(seek = arithmetic, which also makes checkpoint/resume trivial: the
source offset IS the event counter).

Event layout per 50-event epoch (canonical proportions 1:3:46):
  offset 0       -> Person
  offset 1..3    -> Auction
  offset 4..49   -> Bid
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common.chunk import Chunk, StrCol, encode_strings
from risingwave_tpu.common.types import DataType, Field, Schema

PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
BID_PROPORTION = 46
TOTAL_PROPORTION = 50

FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10

NUM_CATEGORIES = 5
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100
HOT_SELLER_RATIO = 100
ACTIVE_PEOPLE = 1000
IN_FLIGHT_AUCTIONS = 100

#: default synthetic start time (unix micros) — 2015-07-15, as in Beam's
#: BASE_TIME, so q5/q7 window math exercises realistic timestamps.
BASE_TIME_US = 1_436_918_400_000_000


# ---------------------------------------------------------------------------
# counter-based randomness

_K1 = np.uint64(0x9E3779B97F4A7C15)
_K2 = np.uint64(0xBF58476D1CE4E5B9)
_K3 = np.uint64(0x94D049BB133111EB)


def _mix(x: jnp.ndarray) -> jnp.ndarray:
    x = (x ^ (x >> np.uint64(30))) * _K2
    x = (x ^ (x >> np.uint64(27))) * _K3
    return x ^ (x >> np.uint64(31))


def _rand(event_id: jnp.ndarray, stream: int) -> jnp.ndarray:
    """uint64 uniform random, keyed on (event id, field stream)."""
    stream_key = np.uint64((stream * int(_K3)) & 0xFFFFFFFFFFFFFFFF)
    return _mix(event_id.astype(jnp.uint64) * _K1 ^ stream_key)


def _rand_int(event_id, stream: int, bound: int) -> jnp.ndarray:
    return (_rand(event_id, stream) % np.uint64(bound)).astype(jnp.int64)


# ---------------------------------------------------------------------------
# id chaining (canonical generator arithmetic, vectorized)


def _last_base0_person_id(event_number: jnp.ndarray) -> jnp.ndarray:
    epoch = event_number // TOTAL_PROPORTION
    offset = jnp.minimum(event_number % TOTAL_PROPORTION, PERSON_PROPORTION - 1)
    return epoch * PERSON_PROPORTION + offset


def _last_base0_auction_id(event_number: jnp.ndarray) -> jnp.ndarray:
    epoch = event_number // TOTAL_PROPORTION
    offset = event_number % TOTAL_PROPORTION
    before_auctions = offset < PERSON_PROPORTION
    epoch = jnp.where(before_auctions, epoch - 1, epoch)
    offset = jnp.where(
        before_auctions,
        AUCTION_PROPORTION - 1,
        jnp.minimum(offset - PERSON_PROPORTION, AUCTION_PROPORTION - 1),
    )
    return epoch * AUCTION_PROPORTION + offset


def _next_base0_person_id(event_number: jnp.ndarray, event_id: jnp.ndarray,
                          stream: int) -> jnp.ndarray:
    """A person among the last ACTIVE_PEOPLE (canonical nextBase0PersonId):
    the people so far follow ``event_number``, the draw among them is
    keyed by ``event_id``."""
    num_people = _last_base0_person_id(event_number) + 1
    active = jnp.minimum(num_people, ACTIVE_PEOPLE)
    lo = num_people - active
    return lo + _rand_int(event_id, stream, ACTIVE_PEOPLE + 1).clip(max=active)


def _next_base0_auction_id(event_id: jnp.ndarray, stream: int) -> jnp.ndarray:
    min_auction = jnp.maximum(
        _last_base0_auction_id(event_id) - IN_FLIGHT_AUCTIONS, 0
    )
    max_auction = _last_base0_auction_id(event_id)
    span = max_auction - min_auction + 1
    return min_auction + (_rand(event_id, stream) % span.astype(jnp.uint64)).astype(
        jnp.int64
    )


#: log-uniform price curve, ``round(100 * 10^(6 i / 1024))`` at 1,025
#: knots.  Built with ``decimal`` (software arithmetic, the same digits
#: on every host), never with a libm ``pow``.
_PRICE_KNOT_BITS = 10


def _price_knots() -> np.ndarray:
    import decimal

    with decimal.localcontext() as ctx:
        ctx.prec = 50
        ln10 = decimal.Decimal(10).ln()
        n = 1 << _PRICE_KNOT_BITS
        return np.asarray([
            int((ln10 * (2 + decimal.Decimal(6 * i) / n)).exp()
                .to_integral_value(decimal.ROUND_HALF_EVEN))
            for i in range(n + 1)
        ], np.int64)


_PRICE_KNOTS = _price_knots()


def _next_price(event_id: jnp.ndarray, stream: int) -> jnp.ndarray:
    """Canonical nextPrice's long tail — 100 * 10^(6 U), U uniform —
    in integer arithmetic alone: the top bits of the draw pick a knot of
    the curve, the next 32 interpolate to the following one.  The chip
    emulates float64 and rounds ``10.0 ** x`` differently from a host
    (5% of prices came out one unit apart), and a source that differs by
    backend breaks every comparison, replay and recovery across them."""
    r = _rand(event_id, stream)
    knot = (r >> np.uint64(64 - _PRICE_KNOT_BITS)).astype(jnp.int32)
    frac = (r >> np.uint64(32 - _PRICE_KNOT_BITS)) & np.uint64(0xFFFFFFFF)
    knots = jnp.asarray(_PRICE_KNOTS)
    lo, hi = knots[knot], knots[knot + 1]
    step = ((hi - lo).astype(jnp.uint64) * frac) >> np.uint64(32)
    return lo + step.astype(jnp.int64)


# ---------------------------------------------------------------------------
# schemas (ref: e2e_test/nexmark/create_sources.slt.part)

BID_SCHEMA = Schema(
    (
        Field("auction", DataType.INT64),
        Field("bidder", DataType.INT64),
        Field("price", DataType.INT64),
        Field("channel", DataType.VARCHAR, str_width=16),
        Field("url", DataType.VARCHAR, str_width=40),
        Field("date_time", DataType.TIMESTAMP),
    )
)

AUCTION_SCHEMA = Schema(
    (
        Field("id", DataType.INT64),
        Field("item_name", DataType.VARCHAR, str_width=24),
        Field("description", DataType.VARCHAR, str_width=32),
        Field("initial_bid", DataType.INT64),
        Field("reserve", DataType.INT64),
        Field("date_time", DataType.TIMESTAMP),
        Field("expires", DataType.TIMESTAMP),
        Field("seller", DataType.INT64),
        Field("category", DataType.INT64),
    )
)

PERSON_SCHEMA = Schema(
    (
        Field("id", DataType.INT64),
        Field("name", DataType.VARCHAR, str_width=24),
        Field("email_address", DataType.VARCHAR, str_width=32),
        Field("credit_card", DataType.VARCHAR, str_width=20),
        Field("city", DataType.VARCHAR, str_width=16),
        Field("state", DataType.VARCHAR, str_width=4),
        Field("date_time", DataType.TIMESTAMP),
    )
)

_CHANNELS = ["Google", "Facebook", "Baidu", "Apple"]
_CITIES = ["Phoenix", "Los Angeles", "San Francisco", "Boise", "Portland",
           "Bend", "Redmond", "Seattle", "Kent", "Cheyenne"]
_STATES = ["AZ", "CA", "ID", "OR", "WA", "WY"]
_FIRST_NAMES = ["Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate",
                "Julie", "Sarah", "Deiter", "Walter"]
_LAST_NAMES = ["Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton",
               "Smith", "Jones", "Noris"]


def _codebook(values: list[str], width: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    data, lens = encode_strings(values, width)
    return jnp.asarray(data), jnp.asarray(lens)


def _gather_str(codebook, idx) -> StrCol:
    data, lens = codebook
    return StrCol(data[idx], lens[idx])


@dataclass(frozen=True)
class NexmarkConfig:
    """Generator knobs (ref NexmarkProperties, nexmark/mod.rs:50)."""

    #: microseconds between consecutive events (event time)
    inter_event_us: int = 10
    base_time_us: int = BASE_TIME_US
    seed: int = 0


class NexmarkGenerator:
    """Vectorized generator addressed by per-table ordinal ranges.

    ``gen_bids(k0, cap)`` returns a Chunk of bids number ``k0..k0+cap``
    (in bid ordinal space), fully on device.  The k-th bid corresponds to
    global event number ``(k // 46) * 50 + 4 + (k % 46)``; analogous maps
    for persons/auctions.  Generation-from-ordinal makes source splits
    and resume offsets pure arithmetic.
    """

    def __init__(self, config: NexmarkConfig = NexmarkConfig()):
        self.config = config
        self._channels = _codebook(_CHANNELS, 16)
        self._cities = _codebook(_CITIES, 16)
        self._states = _codebook(_STATES, 4)
        urls = [f"https://nexmark.io/page{i}/item" for i in range(32)]
        self._urls = _codebook(urls, 40)
        names = [f"{f} {l}" for f in _FIRST_NAMES for l in _LAST_NAMES]
        self._names = _codebook(names, 24)
        emails = [f"{f.lower()}.{l.lower()}@nexmark.io"
                  for f in _FIRST_NAMES for l in _LAST_NAMES]
        self._emails = _codebook(emails, 32)
        items = [f"item-lot-{i:04d}" for i in range(64)]
        self._items = _codebook(items, 24)
        descs = [f"auction description {i}" for i in range(32)]
        self._descs = _codebook(descs, 32)
        cards = [f"{i:04d} {i+1:04d} {i+2:04d} {i+3:04d}" for i in range(16)]
        self._cards = _codebook(cards, 20)
        # jit per-table chunk builders once; ordinal start is traced
        self._gen_bids = jax.jit(self._bids_impl, static_argnums=(1,))
        self._gen_auctions = jax.jit(self._auctions_impl, static_argnums=(1,))
        self._gen_persons = jax.jit(self._persons_impl, static_argnums=(1,))

    # -- event-number math ---------------------------------------------
    def _timestamp(self, event_number: jnp.ndarray) -> jnp.ndarray:
        return (
            np.int64(self.config.base_time_us)
            + event_number * np.int64(self.config.inter_event_us)
        )

    def _event_id(self, event_number: jnp.ndarray) -> jnp.ndarray:
        # seed folds into the randomness key, not the id chain
        return event_number + np.int64(self.config.seed) * np.int64(2**40)

    # -- bids -----------------------------------------------------------
    def _bids_impl(self, k0, cap: int) -> Chunk:
        k = k0 + jnp.arange(cap, dtype=jnp.int64)
        n = (k // BID_PROPORTION) * TOTAL_PROPORTION + PERSON_PROPORTION + \
            AUCTION_PROPORTION + (k % BID_PROPORTION)
        eid = self._event_id(n)
        # hot auction: (ratio-1)/ratio of bids hit the most recent "hot" id
        hot = _rand_int(eid, 1, HOT_AUCTION_RATIO) > 0
        hot_auction = (_last_base0_auction_id(n) // HOT_AUCTION_RATIO) * \
            HOT_AUCTION_RATIO
        auction = jnp.where(hot, hot_auction, _next_base0_auction_id(eid, 2)) + \
            FIRST_AUCTION_ID
        hot_b = _rand_int(eid, 3, HOT_BIDDER_RATIO) > 0
        hot_bidder = (_last_base0_person_id(n) // HOT_BIDDER_RATIO) * \
            HOT_BIDDER_RATIO + 1
        # the cold bidder's people still follow the keyed id: the
        # benchmark's accepted bid reference mirrors it
        bidder = jnp.where(
            hot_b, hot_bidder, _next_base0_person_id(eid, eid, 4)
        ) + FIRST_PERSON_ID
        price = _next_price(eid, 5)
        channel = _gather_str(self._channels, _rand_int(eid, 6, len(_CHANNELS)))
        url = _gather_str(self._urls, _rand_int(eid, 7, 32))
        ts = self._timestamp(n)
        ops = jnp.zeros(cap, jnp.int8)
        valid = jnp.ones(cap, jnp.bool_)
        return Chunk(
            (auction, bidder, price, channel, url, ts), ops, valid, BID_SCHEMA
        )

    def gen_bids(self, k0: int, cap: int) -> Chunk:
        return self._gen_bids(jnp.int64(k0), cap)

    # -- auctions --------------------------------------------------------
    def _auctions_impl(self, k0, cap: int) -> Chunk:
        k = k0 + jnp.arange(cap, dtype=jnp.int64)
        n = (k // AUCTION_PROPORTION) * TOTAL_PROPORTION + PERSON_PROPORTION + \
            (k % AUCTION_PROPORTION)
        eid = self._event_id(n)
        auction_id = _last_base0_auction_id(n) + FIRST_AUCTION_ID
        initial_bid = _next_price(eid, 10)
        reserve = initial_bid + _next_price(eid, 11)
        hot = _rand_int(eid, 12, HOT_SELLER_RATIO) > 0
        hot_seller = (_last_base0_person_id(n) // HOT_SELLER_RATIO) * \
            HOT_SELLER_RATIO
        # a cold seller is one of the people registered by event ``n``,
        # whatever the seed
        seller = jnp.where(
            hot, hot_seller, _next_base0_person_id(n, eid, 13)
        ) + FIRST_PERSON_ID
        category = FIRST_CATEGORY_ID + _rand_int(eid, 14, NUM_CATEGORIES)
        ts = self._timestamp(n)
        # canonical: expires = ts + rand over ~ next in-flight auction horizon
        expires = ts + (_rand_int(eid, 15, 4) + 1) * np.int64(
            self.config.inter_event_us
        ) * TOTAL_PROPORTION * 2
        item = _gather_str(self._items, _rand_int(eid, 16, 64))
        desc = _gather_str(self._descs, _rand_int(eid, 17, 32))
        ops = jnp.zeros(cap, jnp.int8)
        valid = jnp.ones(cap, jnp.bool_)
        return Chunk(
            (auction_id, item, desc, initial_bid, reserve, ts, expires,
             seller, category),
            ops, valid, AUCTION_SCHEMA,
        )

    def gen_auctions(self, k0: int, cap: int) -> Chunk:
        return self._gen_auctions(jnp.int64(k0), cap)

    # -- persons ---------------------------------------------------------
    def _persons_impl(self, k0, cap: int) -> Chunk:
        k = k0 + jnp.arange(cap, dtype=jnp.int64)
        n = k * TOTAL_PROPORTION
        eid = self._event_id(n)
        person_id = _last_base0_person_id(n) + FIRST_PERSON_ID
        name = _gather_str(self._names, _rand_int(eid, 20, len(_FIRST_NAMES) * len(_LAST_NAMES)))
        email = _gather_str(self._emails, _rand_int(eid, 21, len(_FIRST_NAMES) * len(_LAST_NAMES)))
        card = _gather_str(self._cards, _rand_int(eid, 22, 16))
        city = _gather_str(self._cities, _rand_int(eid, 23, len(_CITIES)))
        state = _gather_str(self._states, _rand_int(eid, 24, len(_STATES)))
        ts = self._timestamp(n)
        ops = jnp.zeros(cap, jnp.int8)
        valid = jnp.ones(cap, jnp.bool_)
        return Chunk(
            (person_id, name, email, card, city, state, ts),
            ops, valid, PERSON_SCHEMA,
        )

    def gen_persons(self, k0: int, cap: int) -> Chunk:
        return self._gen_persons(jnp.int64(k0), cap)


class NexmarkSplitReader:
    """A source split: strided ordinal subsequence of one table.

    ref: ``SplitReader`` (src/connector/src/source/base.rs:596) and
    nexmark split assignment.  Split ``i`` of ``m`` reads ordinals
    ``i, i+m, i+2m, …`` — implemented by generating a contiguous ordinal
    block per split instead (equivalent stream content, better locality;
    offsets are still exact for checkpointing).
    """

    def __init__(
        self,
        table: str,
        generator: NexmarkGenerator | None = None,
        chunk_capacity: int = 4096,
        split_id: int = 0,
        num_splits: int = 1,
        offset: int = 0,
    ):
        self.table = table
        self.gen = generator or NexmarkGenerator()
        self.cap = chunk_capacity
        self.split_id = split_id
        self.num_splits = num_splits
        self.offset = offset  # ordinal of the next event for this split
        self._fn = {
            "bid": self.gen.gen_bids,
            "auction": self.gen.gen_auctions,
            "person": self.gen.gen_persons,
        }[table]
        #: traceable generator body — runtimes fuse this into the
        #: fragment step so chunk generation never materializes
        #: standalone in HBM (impl(k0, cap) -> Chunk)
        self.impl = {
            "bid": self.gen._bids_impl,
            "auction": self.gen._auctions_impl,
            "person": self.gen._persons_impl,
        }[table]

    @property
    def events_per_row(self):
        """Global events consumed per emitted row (Fraction) — pacing
        hint so multi-source jobs advance event time in lockstep (the
        reference's single interleaved stream does this implicitly)."""
        from fractions import Fraction
        return {
            "bid": Fraction(TOTAL_PROPORTION, BID_PROPORTION),
            "auction": Fraction(TOTAL_PROPORTION, AUCTION_PROPORTION),
            "person": Fraction(TOTAL_PROPORTION, PERSON_PROPORTION),
        }[self.table]

    @property
    def schema(self) -> Schema:
        return {
            "bid": BID_SCHEMA, "auction": AUCTION_SCHEMA,
            "person": PERSON_SCHEMA,
        }[self.table]

    def next_base(self) -> int:
        """Advance the cursor and return the global ordinal of the next
        cap-row block (host arithmetic; feeds the fused step)."""
        base = (self.offset // self.cap) * self.cap * self.num_splits + \
            self.split_id * self.cap + (self.offset % self.cap)
        self.offset += self.cap
        return base

    def next_chunk(self) -> Chunk:
        return self._fn(self.next_base(), self.cap)

    def state(self) -> dict:
        """Checkpointable offset (rides the barrier, ref SourceChangeSplit)."""
        return {"table": self.table, "split_id": self.split_id,
                "offset": self.offset}
