"""The request stream of a traffic mix, drawn from the seed.

Programs and tenants are uniform, and each program's datasets too, as
the paper's evaluation weighs every program and dataset alike.  Requests
are drawn in blocks of fixed composition (largest-remainder counts), and
the seed only shuffles the order inside each block: every seed sends the
same mix of programs, datasets and tenants, and runs on different seeds
differ by order, not by the amount of work.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Iterator

import numpy as np


def apportion(probs: np.ndarray, total: int) -> np.ndarray:
    """Largest-remainder integer counts summing to ``total``."""
    raw = np.asarray(probs, np.float64) * total
    counts = np.floor(raw).astype(np.int64)
    short = total - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def seed_words(seed: int, *words) -> list[int]:
    """Entropy for ``np.random.default_rng``: any whole seed, however
    large or negative, and a few tags that separate the streams."""
    s = int(seed)
    return [s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, int(s < 0), *words]


def tag(text: str) -> int:
    return zlib.crc32(text.encode())


@dataclasses.dataclass
class Item:
    """One request of the stream."""

    program: str
    rows: int
    tenant: str
    offset: int          # first row of this request's view of its bucket
    check: bool          # its output is compared with the reference


#: draws per dataset in one program's deck of datasets
DECK = 4


def pad_rows(rows: int) -> int:
    """Extra rows a bucket's host arrays carry, so that requests can take
    distinct row windows of one allocation."""
    return max(1, rows // 8)


def request_items(config: dict, traffic: dict, seed: int) -> Iterator[Item]:
    """The endless request stream of ``traffic`` over ``config``.

    Programs are ranked by name and tenants are ``tenant-0 ..``; every
    ``check_every``-th request, from a phase drawn from the seed, is
    checked, so that the checks spread over the whole window."""
    programs = sorted(config["programs"])
    block = int(traffic["block"])
    tenants = [f"tenant-{i}" for i in range(int(traffic["tenants"]))]
    every = int(traffic["check_every"])

    rng = np.random.default_rng(seed_words(seed, tag("requests")))
    phase = int(rng.integers(every))
    prog_counts = apportion(np.full(len(programs), 1 / len(programs)), block)
    ten_counts = apportion(np.full(len(tenants), 1 / len(tenants)), block)
    decks: dict = {}
    i = 0

    def next_rows(name: str) -> int:
        # each program draws its datasets from a deck of DECK of each,
        # reshuffled when it runs out
        deck = decks.get(name)
        if not deck:
            deck = list(np.repeat(config["programs"][name]["rows"], DECK))
            rng.shuffle(deck)
            decks[name] = deck
        return int(deck.pop())

    while True:
        progs = np.repeat(np.arange(len(programs)), prog_counts)
        tens = np.repeat(np.arange(len(tenants)), ten_counts)
        rng.shuffle(progs)
        rng.shuffle(tens)
        off_u = rng.random(block)
        for j in range(block):
            name = programs[progs[j]]
            rows = next_rows(name)
            yield Item(program=name, rows=rows,
                       tenant=tenants[tens[j]],
                       offset=int(off_u[j] * (pad_rows(rows) + 1)),
                       check=i % every == phase)
            i += 1
