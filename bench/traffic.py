"""One general generator of serving traffic, read from a mix's data file.

A mix (``bench/traffic/<name>.json``) gives the loop and the length
distributions:

    {"loop": "open", "rate_per_s": 6.0,
     "prompt_len": {"median": 64, "sigma": 0.8, "min": 16, "max": 256},
     "output_len": {"median": 256, "sigma": 0.7, "min": 32, "max": 1024}}

    {"loop": "closed", "clients": 16, "pool": 64, "prompt_len": ..., ...}

Lengths are log-normal (``median``, ``sigma`` of the log) clipped to
[``min``, ``max``]. An open loop sends requests at ``rate_per_s`` with
stratified exponential gaps (below); a closed loop keeps ``clients``
requests outstanding, each client sending its next request when its last
one finishes. A mix may list under ``assumed`` the parameters it takes
from no published trace, with the reason.

So that a seed changes the order of the work and not its amount, every
seed draws the same multiset: the lengths are the distribution's quantiles
at (i + 1/2)/n and the open loop's gaps are the exponential's quantiles, and
the seed only orders them and draws the prompt's token ids. The order is
shuffled in blocks of ``BLOCK`` consecutive requests: the sorted
values are dealt round-robin into the blocks, so every block holds a
spread of the whole distribution, and the seed shuffles the blocks and the
values within each. Work and arrivals then progress evenly through a
window whatever the seed, while gaps and lengths still come in a random
order inside each block. The gaps are therefore exponential in their
spread but not independent, as Poisson arrivals' would be: every block
of eight holds one gap from each eighth of the distribution, so bursts of
short gaps are rarer than under Poisson. The last block is the same for
every seed, the middle one in one fixed order: the requests that come in
a window's last seconds are the ones its close cuts short, and a tail
drawn by the seed changed the tokens a 51 s window completes by 5% from
seed to seed, where two runs of one seed agreed within 0.4%. That order
comes from one generator of its own, drawn once for the prompt lengths,
once for the answer lengths and once for the gaps, so that the three are
not ranked alike in the last block either. An open loop makes
n = rate x seconds requests; a closed loop cycles through a pool of
``pool`` requests.

A mix with ``"order": "fixed"`` takes the whole order, every block's and
not only the last, from a generator of its own (``ORDER_SEED``), the same
for every seed, as a trace is replayed: the seed then draws the prompts'
token ids alone. Where a queue forms behind long prefills, which requests
come due together decides how long they wait, and the seed's order moved
the time to first token of ``prompt_open``'s 61 requests, its mean by 11
to 13% and its p95 by 15 to 18% from seed to seed, where two runs of one
seed agreed within 2%.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

BLOCK = 8              # consecutive requests that hold a spread of the mix
TAIL_SEED = 0          # orders the last block, the same for every seed
ORDER_SEED = 1         # orders the other blocks of an "order": "fixed" mix

@dataclasses.dataclass
class Item:
    """One request of the mix: when it is due (seconds after the window
    opens; open loop only), its prompt and how many tokens it asks for."""
    due: float
    prompt: List[int]
    max_new: int


def load(path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be open or closed")
    if spec.get("order", "seed") not in ("seed", "fixed"):
        raise ValueError(f"{path}: order must be seed or fixed")
    return spec


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The n log-normal quantiles at (i + 1/2)/n, clipped and rounded."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """The n exponential quantiles at (i + 1/2)/n of mean 1/rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def blocked(values: np.ndarray, rng, tail_rng, block: int) -> np.ndarray:
    """``values`` in the seed's order: dealt round-robin from sorted order
    into ceil(n / block) blocks; the middle block (the lower of two) last,
    in the order ``tail_rng`` draws, and the others before it, blocks and
    their members shuffled by ``rng``."""
    v = np.sort(values)
    nb = max(1, -(-len(v) // block))
    last = (nb - 1) // 2
    blocks = [rng.permutation(v[j::nb]) for j in range(nb) if j != last]
    tail = tail_rng.permutation(v[last::nb])
    return np.concatenate([blocks[j] for j in rng.permutation(nb - 1)]
                          + [tail])


def count(spec: dict, seconds: float) -> int:
    """Requests one run draws."""
    if spec["loop"] == "open":
        return max(1, int(round(spec["rate_per_s"] * seconds)))
    return int(spec["pool"])


def generate(spec: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The run's requests in the order they are sent."""
    n = count(spec, seconds)
    rng = np.random.default_rng(int(seed) & ((1 << 63) - 1))
    order = np.random.default_rng(ORDER_SEED) \
        if spec.get("order") == "fixed" else rng
    tail = np.random.default_rng(TAIL_SEED)
    plen = blocked(quantile_lengths(spec["prompt_len"], n), order, tail,
                   BLOCK)
    olen = blocked(quantile_lengths(spec["output_len"], n), order, tail,
                   BLOCK)
    if spec["loop"] == "open":
        due = np.cumsum(blocked(exp_gaps(spec["rate_per_s"], n), order,
                                tail, BLOCK))
    else:
        due = np.zeros(n)
    return [Item(float(due[i]),
                 rng.integers(1, vocab, size=int(plen[i])).tolist(),
                 int(olen[i])) for i in range(n)]


def buckets(spec: dict, bucket_of) -> List[int]:
    """Every admission bucket the mix's prompt lengths can fall in."""
    d = spec["prompt_len"]
    return sorted({bucket_of(n) for n in range(d["min"], d["max"] + 1)})
