"""Host input arrays of a bucket, made from the seed.

A configuration states each program's arrays as a per-row shape (for
``chunked`` arrays, which the executor splits along their first axis)
or a whole shape (for ``shared`` ones) and a distribution:

  ``"normal"``              standard normal, float32
  ``["uniform", lo, hi]``   uniform on [lo, hi), float32
  ``["bernoulli", p]``      1.0 with probability p, else 0.0, float32
  ``["randint", lo, hi]``   integers in [lo, hi), int32
  ``"dct"``                 the m x m DCT-II basis cos(pi/m (i + 1/2) j)

Arrays are filled in fixed blocks, each from its own seeded generator,
on a few threads (numpy releases the GIL while it fills): the result
depends on the seed and the block size only, never on the thread count.
"""
from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from trafficgen import pad_rows, seed_words, tag

BLOCK = 1 << 22
THREADS = 8


def _fill(out: np.ndarray, dist, words: list[int], lo: int, hi: int) -> None:
    rng = np.random.default_rng(words + [lo // BLOCK])
    view = out[lo:hi]
    n = hi - lo
    if dist == "normal":
        rng.standard_normal(out=view, dtype=np.float32)
    elif dist[0] == "uniform":
        rng.random(out=view, dtype=np.float32)
        view *= np.float32(dist[2] - dist[1])
        view += np.float32(dist[1])
    elif dist[0] == "bernoulli":
        view[:] = rng.random(n, dtype=np.float32) < dist[1]
    elif dist[0] == "randint":
        view[:] = rng.integers(dist[1], dist[2], n, dtype=np.int32)
    else:
        raise ValueError(f"unknown distribution {dist!r}")


def make_array(spec: dict, lead: tuple, words: list[int],
               pool: ThreadPoolExecutor) -> np.ndarray:
    shape = tuple(lead) + tuple(spec["shape"])
    dist = spec["dist"]
    if dist == "dct":
        m = shape[-1]
        return np.cos(math.pi / m * np.outer(np.arange(m) + 0.5,
                                             np.arange(m))).astype(np.float32)
    dtype = np.int32 if dist[0] == "randint" else np.float32
    flat = np.empty(math.prod(shape), dtype)
    futs = [pool.submit(_fill, flat, dist, words, lo,
                        min(lo + BLOCK, flat.size))
            for lo in range(0, flat.size, BLOCK)]
    for f in futs:
        f.result()
    return flat.reshape(shape)


def bucket_data(program: str, spec: dict, rows: int, seed: int,
                pool: ThreadPoolExecutor) -> tuple[dict, dict]:
    """(chunked, shared) host arrays of one (program, rows) bucket; the
    chunked arrays carry ``pad_rows(rows)`` extra rows, of which each
    request takes a window of ``rows``."""
    chunked = {k: make_array(a, (rows + pad_rows(rows),),
                             seed_words(seed, tag(f"{program}@{rows}:{k}")),
                             pool)
               for k, a in spec["chunked"].items()}
    shared = {k: make_array(a, (), seed_words(seed, tag(f"{program}@{rows}:{k}")),
                            pool)
              for k, a in spec["shared"].items()}
    return chunked, shared


def request_view(chunked: dict, offset: int, rows: int) -> dict:
    return {k: a[offset:offset + rows] for k, a in chunked.items()}
