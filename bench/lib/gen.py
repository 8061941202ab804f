"""The one traffic generator: it reads a mix's parameters and makes its inputs.

A traffic mix is a JSON file under ``bench/traffic/``. Its ``kind`` names one
of the two shapes below, and the rest of the file holds the parameters:

* ``closed_loop`` — bulk sorts: ``inputs`` distinct (p, n_per_proc) int32 key
  arrays from the key distribution ``keys``, sorted back to back;
* ``open_loop`` — served requests: arrivals at ``rate_per_s``, sizes drawn in
  blocks of ``block_requests`` by :func:`zipf_sizes` over ``block_keys`` keys,
  keys cycling through ``key_mixes``, in the order that ``schedule_seed``
  draws for every run seed.

The key generators are copies of the paper's §6.3 sets as this repository
implements them (``repro.core.datagen``), so that an edit there cannot move
the yardstick. Everything is made from ``--seed``: the same seed gives the
same inputs, and every seed gives the same amount of work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

INT_MAX = 2**31


# ---------------------------------------------- copied from repro.core.datagen
def _rngs(p: int, seed: int):
    # paper: processor i's seed is 21 + 1001*i
    return [np.random.default_rng(seed + 21 + 1001 * i) for i in range(p)]


def uniform(p: int, n_p: int, seed: int = 0) -> np.ndarray:
    """[U] — uniform in [0, INT_MAX)."""
    return np.stack(
        [r.integers(0, INT_MAX, n_p, dtype=np.int64) for r in _rngs(p, seed)]
    ).astype(np.int32)


def gaussian(p: int, n_p: int, seed: int = 0) -> np.ndarray:
    """[G] — mean of four uniform draws."""
    out = []
    for r in _rngs(p, seed):
        out.append(sum(r.integers(0, INT_MAX, n_p, dtype=np.int64) for _ in range(4)) // 4)
    return np.stack(out).astype(np.int32)


def deterministic_duplicates(p: int, n_p: int, seed: int = 0) -> np.ndarray:
    """[DD] — the first p/2 procs hold lg n everywhere, the next p/4 procs
    lg(n/2), …; the last proc's run is itself halved into runs of lg(n/p),
    lg(n/(2p)), …"""
    n = p * n_p
    lg = int(np.log2(max(n, 2)))
    x = np.zeros((p, n_p), np.int32)
    start, size, v = 0, max(p // 2, 1), lg
    while start < p - 1 and size >= 1:
        x[start : min(start + size, p - 1)] = v
        start += size
        size = max(size // 2, 1)
        v = max(v - 1, 0)
        if size == 1 and start >= p - 1:
            break
    off, run, v = 0, max(n_p // 2, 1), int(np.log2(max(n // p, 2)))
    while off < n_p:
        x[p - 1, off : off + run] = v
        off += run
        run = max(run // 2, 1)
        v = max(v - 1, 0)
    return x


def zipf_keys(p: int, n_p: int, seed: int = 0, alpha: float = 1.5) -> np.ndarray:
    """[zipf] — duplicate-heavy keys, frequency of value v ∝ v^-alpha."""
    return np.stack(
        [np.minimum(r.zipf(alpha, n_p), INT_MAX - 1) for r in _rngs(p, seed)]
    ).astype(np.int32)


def zipf_sizes(n_requests: int, total: int, seed: int = 0, alpha: float = 1.2) -> np.ndarray:
    """Request sizes ∝ rank^-alpha, at least 1, summing to ``total``, shuffled.

    The multiset of sizes depends only on (n_requests, total, alpha); the
    seed only shuffles it.
    """
    if not total >= n_requests >= 1:
        raise ValueError(f"need total >= n_requests >= 1, got {total}, {n_requests}")
    w = 1.0 / np.arange(1, n_requests + 1, dtype=np.float64) ** alpha
    sizes = np.maximum((w / w.sum() * total).astype(np.int64), 1)
    excess = int(sizes.sum()) - total
    order = np.argsort(-sizes)
    i = 0
    while excess > 0:
        j = order[i % n_requests]
        take = min(excess, int(sizes[j]) - 1)
        sizes[j] -= take
        excess -= take
        i += 1
    if excess < 0:
        sizes[order[0]] -= excess
    rng = np.random.default_rng(seed + 21)
    rng.shuffle(sizes)
    return sizes


KEYS = {
    "U": uniform,
    "G": gaussian,
    "DD": deterministic_duplicates,
    "zipf": zipf_keys,
}


# ------------------------------------------------------------- seeding
def substream(seed: int, *path: int) -> int:
    """A 63-bit seed for one part of the traffic, derived from ``--seed``.

    Different parts (the k-th input, the b-th block, warm-up) get
    independent draws, and the run seed may be any non-negative integer.
    """
    ss = np.random.SeedSequence([int(seed) % 2**63, *path])
    return int(ss.generate_state(1, np.uint64)[0]) >> 1


# ------------------------------------------------------------- the mixes
def closed_loop_inputs(mix: Dict, p: int, n_p: int, seed: int) -> List[np.ndarray]:
    """The ``inputs`` distinct (p, n_p) key arrays a closed loop cycles through."""
    make = KEYS[mix["keys"]]
    return [make(p, n_p, seed=substream(seed, 1, k)) for k in range(int(mix["inputs"]))]


def poisson_gaps(rate: float, count: int, rng: np.random.Generator) -> np.ndarray:
    """``count`` inter-arrival gaps of a Poisson stream, in a shuffled order.

    The gaps are the exponential law's quantiles at (k + 1/2)/count, so every
    seed offers the same gaps, and hence the same total time, in another
    order.
    """
    q = (np.arange(count, dtype=np.float64) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    return gaps


def open_loop_sizes(mix: Dict, count: int, seed: int) -> np.ndarray:
    """Request sizes, drawn block by block; each block has the same multiset."""
    per = int(mix["block_requests"])
    blocks = -(-count // per)
    sizes = [
        zipf_sizes(per, int(mix["block_keys"]), seed=substream(seed, 2, b), alpha=float(mix["size_alpha"]))
        for b in range(blocks)
    ]
    return np.concatenate(sizes)[:count]


def open_loop_requests(mix: Dict, seed: int, seconds: float) -> List[Tuple[float, np.ndarray]]:
    """``(due seconds from the window's start, int32 keys)`` for every request.

    ``round(rate · seconds)`` requests, all due inside the window. The order
    of the gaps and of the sizes is drawn from the mix's ``schedule_seed``,
    so that every run seed offers the same schedule and draws only the keys:
    in an open loop the order alone decides which requests share a batch.
    """
    count = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    schedule = int(mix["schedule_seed"])
    rng = np.random.default_rng(substream(schedule, 3))
    due = np.cumsum(poisson_gaps(float(mix["rate_per_s"]), count, rng))
    due = due - due[0]  # the first request is due as the window opens
    sizes = open_loop_sizes(mix, count, schedule)
    mixes = mix["key_mixes"]
    return [
        (float(t), KEYS[mixes[i % len(mixes)]](1, int(s), seed=substream(seed, 4, i))[0])
        for i, (t, s) in enumerate(zip(due, sizes))
    ]


def block_size_set(mix: Dict) -> np.ndarray:
    """The sizes every block of an open-loop mix holds (any seed)."""
    return np.sort(zipf_sizes(int(mix["block_requests"]), int(mix["block_keys"]), alpha=float(mix["size_alpha"])))
