"""The traffic generator: the same seed gives the same requests, another
seed the same sizes on another arrival path, every mix file draws the
lengths it declares, and an arrival process is a file of its own."""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import traffic as T  # noqa: E402

MIXES = sorted(glob.glob(os.path.join(os.path.dirname(T.__file__),
                                      "traffic", "*.json")))
SEED = 2**31 + 7           # seeds may pass 32 signed bits


def _mix(path, warm=True):
    with open(path) as f:
        mix = json.load(f)
    if not warm:
        mix.pop("warm_start", None)
    return mix


def _sizes(tr):
    """The multisets of prompt lengths (of system prompts chosen, where a
    system prompt leads, since the user turn's pairing with it varies)
    and of replies."""
    if tr.requests[0].system >= 0:
        prompts = sorted(r.system for r in tr.requests)
    else:
        prompts = sorted(len(r.prompt) for r in tr.requests)
    return prompts, sorted(r.max_new for r in tr.requests)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_same_seed_same_requests(path):
    mix = _mix(path)
    a = T.generate(mix, SEED, 50.0, 151552)
    b = T.generate(mix, SEED, 50.0, 151552)
    assert [(r.due_s, r.prompt, r.max_new, r.warm) for r in a.requests] \
        == [(r.due_s, r.prompt, r.max_new, r.warm) for r in b.requests]


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_other_seed_same_sizes_other_order(path):
    """Without the warm start's cut, two seeds draw the same sizes in
    another order, each within a block of its place in the base order."""
    mix = _mix(path, warm=False)
    a = T.generate(mix, SEED, 50.0, 151552)
    b = T.generate(mix, SEED + 1, 50.0, 151552)
    assert _sizes(a) == _sizes(b)
    assert [r.max_new for r in a.requests] != [r.max_new for r in b.requests]
    assert [r.due_s for r in a.requests] != [r.due_s for r in b.requests]
    assert a.requests[0].prompt != b.requests[0].prompt
    n = len(a.requests)
    for i in range(0, n - n % T.BLOCK, T.BLOCK):
        assert sorted(r.max_new for r in a.requests[i:i + T.BLOCK]) \
            == sorted(r.max_new for r in b.requests[i:i + T.BLOCK])
    assert a.offered_tok_s == b.offered_tok_s > 0


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_lengths_follow_the_mix(path):
    mix = _mix(path, warm=False)
    tr = T.generate(mix, SEED, 50.0, 151552)
    out = np.asarray([r.max_new for r in tr.requests])
    o = mix["output"]
    assert out.min() >= o["min"] and out.max() <= o["max"]
    n = len(tr.requests)
    span = 50.0 + mix.get("lead_in_s", 0.0)
    rate = mix["arrivals"]["rate_per_s"]
    assert n == int(rate * span)
    due = np.asarray([r.due_s for r in tr.requests])
    assert (np.diff(due) >= 0).all() and 0 < due[-1] < span
    # the offered rate is the mix's
    assert abs(n / due[-1] - rate) < 0.05 * rate
    user = np.asarray([len(r.prompt) for r in tr.requests])
    if mix.get("system_prompts"):
        assert len({r.system for r in tr.requests}) \
            <= mix["system_prompts"]["count"]
    elif mix["prompt"]["dist"] == "lognormal":
        assert abs(np.median(user) / mix["prompt"]["median"] - 1) < 0.1
    if o["dist"] == "lognormal":
        assert abs(np.median(out) / o["median"] - 1) < 0.1


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_warm_start_holds_the_live_requests(path):
    """The warm requests are those of the history still decoding when the
    lead-in starts: all due at its start, each with part of a reply, about
    rate x mean life of them."""
    mix = _mix(path)
    tr = T.generate(mix, SEED, 50.0, 151552)
    warm = tr.warm
    assert warm and all(r.due_s == 0.0 for r in warm)
    assert tr.requests[:len(warm)] == warm
    assert all(1 <= r.max_new <= mix["output"]["max"] for r in warm)
    itl = mix["warm_start"]["itl_s"]
    rate = mix["arrivals"]["rate_per_s"]
    replies = T.quantile_lengths(mix["output"], 4096)
    live = rate * itl * replies.mean()
    assert 0.6 * live < len(warm) < 1.4 * live


def test_local_order_keeps_blocks():
    a = T.local_order(103, SEED, 1)
    b = T.local_order(103, SEED + 1, 1)
    assert sorted(a) == list(range(103)) and (a != b).any()
    for i in range(0, 103, T.BLOCK):
        assert sorted(a[i:i + T.BLOCK]) == sorted(b[i:i + T.BLOCK])


def test_balanced_order():
    rng = np.random.default_rng(0)
    n = 8 * 13 + 5
    a = T.balanced_order(n, rng)
    b = T.balanced_order(n, rng)
    assert sorted(a) == list(range(n)) and (a != b).any()
    stratum = np.zeros(n, int)
    for s, idx in enumerate(np.array_split(np.arange(n), T.STRATA)):
        stratum[idx] = s
    for i in range(0, 8 * 13, T.STRATA):
        assert sorted(stratum[a[i:i + T.STRATA]]) == list(range(T.STRATA))


def test_arrival_process_is_a_file(tmp_path, monkeypatch):
    """A mix names its arrival process; a new process is a new file in
    ``arrivals/``, with no edit to the generator."""
    (tmp_path / "even.py").write_text(
        "import numpy as np\n\n\ndef gaps(spec, n):\n"
        "    return np.full(n, 1.0 / spec['rate_per_s'])\n")
    monkeypatch.setattr(T, "ARRIVALS", str(tmp_path))
    mix = {"arrivals": {"process": "even", "rate_per_s": 2.0},
           "prompt": {"dist": "uniform", "min": 4, "max": 8},
           "output": {"dist": "uniform", "min": 4, "max": 8}}
    due = [r.due_s for r in T.generate(mix, SEED, 10.0, 100).requests]
    assert len(due) == 20 and np.allclose(np.diff(due), np.diff(due)[0])


def test_quantile_lengths():
    x = T.quantile_lengths({"dist": "lognormal", "median": 100,
                            "sigma": 1.0, "min": 10, "max": 500}, 1001)
    assert x[500] == 100 and x.min() >= 10 and x.max() == 500
    assert (np.diff(x) >= 0).all()
    u = T.quantile_lengths({"dist": "uniform", "min": 10, "max": 20}, 4)
    assert u.tolist() == [11, 14, 16, 19]


def test_zipf_counts():
    c = T.zipf_counts(100, 16, 1.1)
    assert c.sum() == 100 and (np.diff(c) <= 0).all() and c[0] > 3 * c[-1]


def test_shared_prefixes_are_shared():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 4.0},
           "system_prompts": {"count": 3, "zipf_s": 1.1,
                              "length": {"dist": "uniform", "min": 30,
                                         "max": 60}},
           "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 20},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 20}}
    tr = T.generate(mix, SEED, 10.0, 1000)
    by = {}
    for r in tr.requests:
        by.setdefault(r.system, []).append(r.prompt)
    assert set(by) <= {0, 1, 2} and len(by[0]) >= len(by.get(2, []))
    for prompts in by.values():
        n = min(len(p) for p in prompts) - 20
        assert all(p[:n] == prompts[0][:n] for p in prompts)
