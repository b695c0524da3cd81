"""The one traffic generator: turns a mix file (``traffic/<mix>.json``) and a
seed into the requests of one run. Open loop: independent users, each
request due at a time fixed by the arrival process, whatever the engine
does.

Every seed gets the same multiset of sizes and inter-arrival gaps, drawn
at stratified quantiles of the mix's distributions, in one base order
fixed by the mix (``balanced_order``: each quantity is split into
``STRATA`` strata of its quantiles, and every run of ``STRATA``
consecutive arrivals holds one value from each stratum; sizes and gaps
are ordered apart), which the seed shuffles within blocks of ``BLOCK``
consecutive arrivals. The seed cannot move the work much more: a reply
here lives for tens of seconds and only some ten requests are due in a
window, so an order drawn afresh for each seed changes which long
requests are live in the window, and with it the cell's rates by 16-44%
from seed to seed (PERF.md, section 6).

Mix keys:

* ``arrivals``: ``{"process": <name>, "rate_per_s", ...}``: the arrival
  process, a module ``arrivals/<name>.py`` whose ``gaps(spec, n)`` returns
  ``n`` inter-arrival gaps (seconds) at stratified quantiles.
* ``lead_in_s`` (optional): seconds of the same traffic before the
  measured window.
* ``warm_start`` (optional): ``{"itl_s"}``: the engine starts near its
  steady state rather than empty. Arrivals are drawn from a history of
  ``output.max * itl_s`` seconds before the lead-in too; those that would
  still be decoding when the lead-in starts (fewer than ``reply`` tokens
  in ``age / itl_s``) are submitted at its start with the rest of their
  reply, and the others are dropped.
* ``prompt`` / ``output``: a length distribution, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}``.
* ``system_prompts`` (optional): ``{"count", "length": <dist>,
  "zipf_s"}``: each request starts with one of ``count`` shared system
  prompts, chosen with Zipf popularity; ``prompt`` is then the user turn.
  Which length each system prompt has is fixed by the mix (the deployment
  defines its prompts); the seed draws their tokens.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import os
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_NORMAL = NormalDist()
STRATA = 8
BLOCK = 4
ARRIVALS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "arrivals")


def quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles (i + 0.5) / n of ``dist``,
    clipped to [min, max], in ascending order."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.asarray([_NORMAL.inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def arrival_gaps(spec: Dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of the process ``spec["process"]``."""
    path = os.path.join(ARRIVALS, spec["process"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        f"arrivals_{spec['process']}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return np.asarray(mod.gaps(spec, n), dtype=np.float64)


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """Split ``n`` requests over ``k`` items with Zipf(s) popularity,
    largest remainders first, so the counts add up to ``n``."""
    w = 1.0 / np.arange(1, k + 1) ** s
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts


def local_order(n: int, seed: int, salt: int) -> np.ndarray:
    """A permutation of ``range(n)``: the mix's balanced base order,
    shuffled by ``seed`` within blocks of ``BLOCK`` consecutive places."""
    out = balanced_order(n, _rng(0, 8 + salt))
    rng = _rng(seed, salt)
    for i in range(0, n, BLOCK):
        out[i:i + BLOCK] = rng.permutation(out[i:i + BLOCK])
    return out


def balanced_order(n: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` (indices of values in ascending order)
    in which every run of ``STRATA`` consecutive places holds one index
    from each of ``STRATA`` contiguous strata: the stratum's member and the
    order within the run drawn from ``rng``."""
    block, key = [], []
    for stratum in np.array_split(np.arange(n), STRATA):
        members = rng.permutation(stratum)
        block.append(np.arange(len(members)))
        key.append(members)
    block = np.concatenate(block)
    members = np.concatenate(key)
    tie = rng.random(len(members))
    return members[np.lexsort((tie, block))]


@dataclasses.dataclass
class Req:
    due_s: float                 # from the lead-in's start; warm: 0
    prompt: List[int]
    max_new: int
    system: int = -1             # shared-prefix: the system prompt used
    warm: bool = False           # part of the state the run starts from


@dataclasses.dataclass
class Traffic:
    requests: List[Req]          # in due order, warm requests first
    offered_tok_s: float         # rate x mean reply: what an engine in
    #                              steady state serves

    @property
    def warm(self) -> List[Req]:
        return [r for r in self.requests if r.warm]


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([salt, seed % 2**63])


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=int(n)).tolist()


def generate(mix: Dict, seed: int, seconds: float, vocab: int) -> Traffic:
    """The requests of one run with a window of ``seconds`` seconds."""
    lead_in = mix.get("lead_in_s", 0.0)
    warm = mix.get("warm_start")
    history = mix["output"]["max"] * warm["itl_s"] if warm else 0.0
    span = history + lead_in + seconds
    arr = mix["arrivals"]
    n = max(STRATA, int(math.floor(arr["rate_per_s"] * span)))
    gaps = np.sort(arrival_gaps(arr, n))[local_order(n, seed, 1)]
    # scale the gaps so the last request is due just inside the window
    due = np.cumsum(gaps) * (span * (n - 0.5) / n) / gaps.sum() - history
    replies = quantile_lengths(mix["output"], n)[local_order(n, seed, 2)]
    prompts = quantile_lengths(mix["prompt"], n)[local_order(n, seed, 3)]
    tok = _rng(seed, 4)
    sys_ids = np.full(n, -1)
    systems: List[List[int]] = []
    sp = mix.get("system_prompts")
    if sp:
        lens = _rng(0, 9).permutation(
            quantile_lengths(sp["length"], sp["count"]))
        systems = [_tokens(tok, m, vocab) for m in lens]
        ids = np.repeat(np.arange(sp["count"]),
                        zipf_counts(n, sp["count"], sp["zipf_s"]))
        sys_ids = ids[local_order(n, seed, 5)]
    reqs = []
    for i in range(n):
        reply, t = int(replies[i]), float(due[i])
        if t < 0:
            done = int(-t / warm["itl_s"])
            if done >= reply:
                continue                   # finished before the lead-in
            reply, t = reply - done, 0.0
        prefix = systems[sys_ids[i]] if sys_ids[i] >= 0 else []
        reqs.append(Req(t, prefix + _tokens(tok, prompts[i], vocab), reply,
                        system=int(sys_ids[i]), warm=due[i] < 0))
    return Traffic(reqs, arr["rate_per_s"] * float(replies.mean()))


def describe(tr: Traffic) -> Dict[str, Optional[float]]:
    """Sizes of the generated traffic, for the run's log."""
    p = [len(r.prompt) for r in tr.requests]
    o = [r.max_new for r in tr.requests]
    return {"requests": len(tr.requests), "warm": len(tr.warm),
            "prompt_tokens": sum(p), "prompt_max": max(p),
            "output_tokens": sum(o), "output_max": max(o),
            "last_due_s": tr.requests[-1].due_s if tr.requests else None}
