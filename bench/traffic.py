"""The one traffic generator: turns a mix file's parameters and a seed
into requests and ingest blocks.

Every seed gets the same work: an open loop's arrival schedule (its
gaps, tenants and seed counts, in order) is fixed by the mix; the seed
draws the documents and the terms asked for.

Mix parameters read here:

- ``loop``: ``"open"`` (requests due on a schedule, ``rate_qps``) or
  ``"closed"`` (``clients`` outstanding requests, each sent when the
  previous one is answered);
- ``tenants``: ``[{"name", "share", "newest_docs"?}]``; a tenant with
  ``newest_docs`` is pinned to a scope of the corpus' newest documents;
- ``seeds``: ``{"counts": [...], "top": n}``: each request has one of
  ``counts`` seed terms (in equal shares) from its tenant's ``top`` most
  frequent terms: drawn at random in an open loop, walked through in a
  seeded order in a closed one;
- ``ingest`` (optional): ``{"block_docs", "period_s"}``: a block of new
  documents every ``period_s`` seconds, each followed by a probe query
  seeded with the block's most frequent term.

The harness reads the rest of a mix: ``method``, ``deadline_ms``,
``max_queue_depth`` (the server's), ``check_sample`` and
``probe_sample`` (answers compared), ``controls``.
"""
from __future__ import annotations

import math
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from bench.corpus import rng_for


#: the one arrival schedule of every open-loop mix: runs of a cell differ
#: in their documents and terms, not in when requests arrive, so that the
#: spread between runs is the system's and not the schedule's
SCHEDULE_SEED = 0


class Planned(NamedTuple):
    due_s: float              # seconds after the window opens
    tenant: str
    seeds: Tuple[int, ...]


def _tiled(values: Sequence, n: int, rng: np.random.Generator) -> list:
    reps = -(-n // len(values))
    return [values[i] for i in rng.permutation(
        np.tile(np.arange(len(values)), reps)[:n])]


def _tenant_slots(mix: dict, n: int, rng: np.random.Generator) -> List[str]:
    """``n`` tenant names in the mix's shares, in a seeded order."""
    tenants = mix["tenants"]
    shares = np.asarray([t["share"] for t in tenants], np.float64)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[np.argsort(-shares, kind="stable")[:n - counts.sum()]] += 1
    names = np.repeat(np.arange(len(tenants)), counts)
    return [tenants[i]["name"] for i in rng.permutation(names)]


def open_loop(mix: dict, seconds: float, seed: int,
              pools: dict) -> List[Planned]:
    """Requests due in ``[0, seconds)``: ``round(rate * seconds)`` of
    them, with exponential gaps taken at fixed quantiles (a Poisson
    process with its count fixed).  The schedule (the order of the gaps,
    of the tenants and of the seed counts) is the mix's alone, the same
    for every seed, as a replayed arrival trace is: the seed draws the
    terms asked for."""
    plan = rng_for(SCHEDULE_SEED, 2)
    n = max(1, int(round(mix["rate_qps"] * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    gaps = plan.permutation(gaps)
    due = np.concatenate(([0.0], np.cumsum(gaps)[:-1]))
    tenants = _tenant_slots(mix, n, plan)
    counts = _tiled(mix["seeds"]["counts"], n, plan)
    rng = rng_for(seed, 2)
    return [Planned(float(d), t, draw_seeds(rng, pools[t], c))
            for d, t, c in zip(due, tenants, counts)]


def draw_seeds(rng: np.random.Generator, pool: np.ndarray,
               count: int) -> Tuple[int, ...]:
    return tuple(int(s) for s in rng.choice(pool, size=count, replace=False))


class SeedWalk:
    """Closed-loop seeds: each tenant's top terms handed out in a seeded
    order, ``count`` consecutive terms a request, round and round."""

    def __init__(self, mix: dict, seed: int, pools: dict):
        rng = rng_for(seed, 3)
        self.counts = mix["seeds"]["counts"]
        self.tenants = _tenant_slots(mix, 4096, rng)
        self.walks = {t: rng.permutation(p) for t, p in pools.items()}
        self.i = 0

    def next(self) -> Tuple[str, Tuple[int, ...]]:
        tenant = self.tenants[self.i % len(self.tenants)]
        count = self.counts[self.i % len(self.counts)]
        walk = self.walks[tenant]
        seeds = tuple(int(walk[(self.i + j) % len(walk)])
                      for j in range(count))
        self.i += 1
        return tenant, seeds


def n_blocks(mix: dict, seconds: float) -> int:
    """Blocks ingested in a window: one every ``period_s``, after the
    window opens and before it closes."""
    ing: Optional[dict] = mix.get("ingest")
    if not ing:
        return 0
    return max(0, math.ceil(seconds / ing["period_s"]) - 1)


def top_terms(df: np.ndarray, n: int) -> np.ndarray:
    """The ``n`` most frequent terms that occur at all (ties to the lower
    id)."""
    order = np.argsort(-df, kind="stable")
    return order[:min(n, int(np.count_nonzero(df)))]
