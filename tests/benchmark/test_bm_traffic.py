"""The generator: the same seed gives the same schedule, another seed
another order of the same work."""

import json
import os

import pytest

from bm_paths import BENCH, FIXTURES

from benchmark.harness import cells, traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))


def _params(mix):
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests_other_seed_other_texts(mix):
    """A seed chooses the texts (and the weights), never the sizes, the
    arrival times or their order: which request meets which burst is the
    work, and a seed that changes the work reads as noise."""
    p = _params(mix)
    a = traffic.schedule(p, 50)
    assert a == traffic.schedule(p, 50)
    assert len(a) == traffic.n_requests(p, 50)
    big = 2 ** 31 + 17
    texts = lambda seed: [traffic.messages(p, seed, r) for r in a[:20]]  # noqa
    assert texts(big) == texts(big)
    assert texts(big) != texts(7)
    assert [[len(m["content"]) for m in ms] for ms in texts(big)] == [
        [len(m["content"]) for m in ms] for ms in texts(7)]


def _lengths_hold(p, running):
    """Every request of the mix inside its own limits and inside the
    context of every cell that runs it (``cells.context_tokens``: the
    cell's largest page bucket in tokens). A mix no cell runs has no
    context to be held to, and fails."""
    assert running, "no cell runs this mix"
    context = min(cells.context_tokens(cell) for cell in running)
    for r in traffic.schedule(p, 50):
        assert p["prompt_len"]["min"] <= r["prompt_len"] \
            <= p["prompt_len"]["max"]
        assert p["output_len"]["min"] <= r["output_len"] \
            <= p["output_len"]["max"]
        assert r["prompt_len"] + r["output_len"] < context


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_inside_their_limits_and_the_context(mix):
    running = [cells.load_cell(w["name"])
               for w in cells.load_benchmark()["workloads"]
               if w["traffic"] == mix]
    _lengths_hold(_params(mix), running)


# prompts of 6k-12k and outputs of 512-1,536 (ISSUE 45: the mix of a
# window-attention cell): 13,824 tokens at the most
LONG = os.path.join(FIXTURES, "long-decode.json")
HOLDS = {"engine": {"page_size": 64, "page_buckets": [64, 217]}}   # 13,888
SHORT = {"engine": {"page_buckets": [64]}}      # the program's page of 64


@pytest.mark.parametrize("running, said", [
    ([HOLDS], None), ([HOLDS, SHORT], "< 4096"), ([SHORT], "< 4096"),
    ([], "no cell runs this mix")],
    ids=["holds", "one-of-two-does-not", "does-not", "no-cell"])
def test_a_long_mix_is_held_to_the_cells_that_run_it(running, said):
    with open(LONG) as f:
        p = json.load(f)
    longest = max(r["prompt_len"] + r["output_len"]
                  for r in traffic.schedule(p, 50))
    assert 12288 < longest <= 13824
    if said is None:
        _lengths_hold(p, running)
    else:
        with pytest.raises(AssertionError, match=said):
            _lengths_hold(p, running)


@pytest.mark.parametrize("mix", [m for m in MIXES
                                 if _params(m)["loop"] == "open"])
def test_open_loop_offers_the_stated_rate_inside_the_window(mix):
    p = _params(mix)
    s = traffic.schedule(p, 50)
    due = [r["due_s"] for r in s]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 50
    assert len(s) == round(p["rate_rps"] * 50)


def test_closed_loop_has_no_due_times():
    p = {"loop": "closed", "clients": 4, "pool": 32, "base_seed": 1,
         "prompt_len": {"dist": "uniform", "min": 8, "max": 16},
         "output_len": {"dist": "fixed", "value": 5}}
    s = traffic.schedule(p, 10)
    assert len(s) == 32 and all(r["due_s"] is None for r in s)
    assert {r["output_len"] for r in s} == {5}


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "gamma", "min": 1, "max": 2}, 0.5)


def test_quantiles_follow_the_distribution():
    d = {"dist": "lognormal", "median": 384, "sigma": 0.9, "min": 16,
         "max": 3072}
    assert traffic.quantile(d, 0.5) == 384
    assert traffic.quantile(d, 1e-9) == 16
    assert traffic.quantile(d, 1 - 1e-9) == 3072
    assert traffic.quantile({"dist": "uniform", "min": 10, "max": 20},
                            0.5) == 15


def test_messages_have_exact_lengths_and_share_prefixes():
    p = _params("shared-prefix")
    s = traffic.schedule(p, 50)
    by_prefix = {}
    for r in s[:40]:
        m = traffic.messages(p, 5, r)
        # BOS + characters == prompt_len under the byte tokenizer
        assert sum(len(x["content"]) for x in m) + 1 == r["prompt_len"]
        assert all(x["content"].isascii() for x in m)
        assert len(m[0]["content"]) == p["shared_prefix"]["chars"]
        by_prefix.setdefault(r["prefix"], set()).add(m[0]["content"])
    assert all(len(v) == 1 for v in by_prefix.values())
    assert len({next(iter(v)) for v in by_prefix.values()}) == len(by_prefix)
    # another seed writes other texts
    assert traffic.messages(p, 6, s[0]) != traffic.messages(p, 5, s[0])
    # Zipf: the most popular prefix gets about 1/H(8) = 37% of requests
    top = sum(r["prefix"] == 0 for r in s) / len(s)
    assert 0.3 < top < 0.45
