"""NEXmark Query 1 (currency conversion): Bid events, their wire form and
the plain reference. numpy only; shares no code with the engine.

``SELECT auction, bidder, 0.908 * price, dateTime FROM bid``. Every bid
lands one row, so output = input and the result path does the work.

Bids follow the Beam suite's generator as far as Q1 can tell them apart
(the file ``configs/nexmark-q1.json`` lists what is assumed): of every
50 events 46 are bids and only those are sent; an auction id is the hot
one (the newest multiple of 100) for 1 bid in 2, else one of the 100
newest; a bidder is the hot one for 3 bids in 4, else one of the 1,000
newest; price = round(100 * 10**(6 u)); ``extra`` pads the record. Lines
are fixed width (numbers padded on the left with spaces JSON allows) so
a chunk renders as one uint8 matrix with no per-row Python."""

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import wire

FIRST_AUCTION_ID, FIRST_PERSON_ID = 1000, 1000
IN_FLIGHT_AUCTIONS, ACTIVE_PEOPLE = 100, 1000
RATE = np.float32(0.908)

_TEMPLATE = (
    b'{"auction":AAAAAAAAA,"bidder":BBBBBBBBB,"price":PPPPPPPPP,'
    b'"channel":"channel-CCCC","url":"https://www.nexmark.com/ch/CCCC",'
    b'"dateTime":TTTTTTTTTTTTT,"extra":"XXXXXXXXXXXXXXXXXXXXXXXXX"}\n'
)


_AUCTION, _BIDDER, _PRICE = (
    wire.field(_TEMPLATE, b"AAAAAAAAA"), wire.field(_TEMPLATE, b"BBBBBBBBB"),
    wire.field(_TEMPLATE, b"PPPPPPPPP"))
_CHANNEL = wire.field(_TEMPLATE, b"CCCC")
_URL = slice(_TEMPLATE.rindex(b"CCCC"), _TEMPLATE.rindex(b"CCCC") + 4)
_TIME, _EXTRA = (wire.field(_TEMPLATE, b"TTTTTTTTTTTTT"),
                 wire.field(_TEMPLATE, b"X" * 25))
LINE_BYTES = len(_TEMPLATE)


def make_events(seed, n: int, first: int = 0) -> Dict[str, np.ndarray]:
    """Bids ``first`` .. ``first + n`` of the stream (ids grow with the
    event number, so a block has to know where it starts)."""
    rng = np.random.default_rng(seed)
    event_no = (np.arange(first, first + n, dtype=np.int64) * 50) // 46
    last_auction = FIRST_AUCTION_ID + (event_no // 50) * 3
    last_person = FIRST_PERSON_ID + event_no // 50
    bits = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    pick = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    auction = np.where(
        bits & 1, (last_auction // 100) * 100,
        np.maximum(FIRST_AUCTION_ID,
                   last_auction - (pick & 0xFFFF) % IN_FLIGHT_AUCTIONS))
    bidder = np.where(
        (bits >> 1) & 3, (last_person // 100) * 100 + 1,
        np.maximum(FIRST_PERSON_ID,
                   last_person - (pick >> 16) % ACTIVE_PEOPLE))
    price = np.round(100.0 * 10.0 ** (6.0 * rng.random(n))).astype(np.int64)
    return {
        "auction": auction.astype(np.int32), "bidder": bidder.astype(np.int32),
        "price": price.astype(np.int32),
        "channel": ((bits >> 3) % 10_000).astype(np.int32),
        "extra": (65 + (bits >> 17) % 26).astype(np.uint8),
    }


def lines(ev: Dict[str, np.ndarray], lo: int, hi: int) -> bytes:
    """``ev["due_ms"]`` (epoch ms the event is due to be sent, set by the
    harness) is the bid's ``dateTime``, its creation stamp."""
    out = np.tile(np.frombuffer(_TEMPLATE, np.uint8), (hi - lo, 1))
    out[:, _AUCTION] = wire.digits(ev["auction"][lo:hi], 9, 32)
    out[:, _BIDDER] = wire.digits(ev["bidder"][lo:hi], 9, 32)
    out[:, _PRICE] = wire.digits(ev["price"][lo:hi], 9, 32)
    channel = wire.digits(ev["channel"][lo:hi], 4, 48)
    out[:, _CHANNEL] = channel
    out[:, _URL] = channel
    out[:, _TIME] = wire.digits(ev["due_ms"][lo:hi], 13, 48)
    out[:, _EXTRA] = ev["extra"][lo:hi, None]
    return out.tobytes()


def alert_events(ev: Dict[str, np.ndarray], lo: int, hi: int) -> np.ndarray:
    """Every bid lands a Q1 row."""
    return np.arange(lo, hi)


def reference(
    ev: Dict[str, np.ndarray], batches: Sequence[Tuple[int, int]],
    cast=lambda x: x,
) -> Dict[str, List[Dict[str, np.ndarray]]]:
    """Per batch the Q1 rows in stream order. The flow multiplies in
    float32 (the engine's double is float32: x64 is off by design).
    ``cast`` narrows the factors and the product (the low-precision
    control)."""
    bounds = np.concatenate([[0], np.cumsum([n for _t, n in batches])])
    rows = []
    for k in range(len(batches)):
        lo, hi = bounds[k], bounds[k + 1]
        price = cast(ev["price"][lo:hi].astype(np.float32))
        rows.append({
            "auction": ev["auction"][lo:hi].astype(np.int64),
            "bidder": ev["bidder"][lo:hi].astype(np.int64),
            "price": cast(price * cast(RATE)).astype(np.float64),
            "dateTime": ev["due_ms"][lo:hi].astype(np.int64),
        })
    return {"Q1": rows}


def control(ev, batches):
    """The reference in the nearest precision below the float32 the
    configuration states: bfloat16 factors and product."""
    return reference(ev, batches, cast=wire.bfloat16)


COLUMNS = {
    "Q1": {"auction": "exact", "bidder": "exact", "price": "price_rel_gap",
           "dateTime": "exact"},
}

LIMITS = {
    "rows_differ": 0, "offset_off_boundary": 0,
    # one float32 multiplication against the same in numpy
    "price_rel_gap": 1e-6,
}
