"""The traffic generator: seeded, within each mix's stated ranges, and
independent of anything the program returns."""
import json

import numpy as np
import pytest

from benchlib import traffic as tr
from smoke_cell import BENCH

MAX_LEN, SLOTS, VOCAB, PAGE = 4096, 48, 151936, 64


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def gen(name, seed, seconds=40.0):
    return tr.generate(mix(name), seed, seconds, vocab=VOCAB,
                       max_len=MAX_LEN, slots=SLOTS)


def all_requests(t):
    return t.requests + t.first_wave + t.warm


def same(a, b):
    return len(a) == len(b) and all(
        np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
        and x.due == y.due for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["chat-sessions", "reasoning-batch"])
def test_same_seed_same_requests_other_seed_others(name):
    a, b = gen(name, 2 ** 33 + 5), gen(name, 2 ** 33 + 5)
    c = gen(name, 2 ** 33 + 6)
    assert same(all_requests(a), all_requests(b))
    assert not same(all_requests(a), all_requests(c))


@pytest.mark.parametrize("name", ["chat-sessions", "reasoning-batch"])
def test_every_seed_gives_the_same_work(name):
    """Seeds pick token ids (and order a closed loop's backlog); they
    move no length, due time or shared prefix."""
    def sizes(t, key=lambda r: r):
        return sorted(key((len(r.prompt), r.max_new, r.due, r.prefix_len))
                      for r in t.requests + t.first_wave + t.warm)
    a, b = gen(name, 1), gen(name, 2 ** 31 + 99)
    assert sizes(a) == sizes(b)
    if name == "chat-sessions":   # in the same order, at the same times
        assert [(len(r.prompt), r.max_new, r.due) for r in a.requests] == \
            [(len(r.prompt), r.max_new, r.due) for r in b.requests]


@pytest.mark.parametrize("name", ["chat-sessions", "reasoning-batch"])
def test_lengths_stay_in_the_stated_ranges(name):
    m = mix(name)
    t = gen(name, 7)
    out = m["output_tokens"]
    for r in t.requests:
        assert out["min"] <= r.max_new <= out["max"]
        assert len(r.prompt) + r.max_new <= MAX_LEN
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < VOCAB
    for r in t.first_wave:
        assert len(r.prompt) + r.max_new <= MAX_LEN
    if name == "reasoning-batch":
        p = m["prompt_tokens"]
        assert all(p["min"] <= len(r.prompt) <= p["max"]
                   for r in t.requests)
        assert len(t.first_wave) == SLOTS
    else:
        dues = [r.due for r in t.requests]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 40


def test_reasoning_prompts_share_no_full_page():
    t = gen("reasoning-batch", 3)
    firsts = [r.prompt[:PAGE].tobytes() for r in t.requests + t.first_wave
              if len(r.prompt) >= PAGE]
    assert len(set(firsts)) == len(firsts)


def test_chat_turns_share_their_system_prompt_and_history():
    m = mix("chat-sessions")
    t = gen("chat-sessions", 11)
    prefixes = {r.prefix_id: r.prompt for r in t.warm if r.idx < 0}
    assert len(prefixes) == m["shared_prefix"]["count"]
    lo, hi = m["shared_prefix"]["tokens"]["min"], \
        m["shared_prefix"]["tokens"]["max"]
    assert all(lo <= len(p) <= hi for p in prefixes.values())
    by_session = {}
    for r in t.requests:
        p = prefixes[r.prefix_id]
        assert np.array_equal(r.prompt[:len(p)], p)
        by_session.setdefault(r.session, []).append(r)
    multi = [v for v in by_session.values() if len(v) > 1]
    assert multi
    for turns in multi:
        for a, b in zip(turns, turns[1:]):
            # the next turn carries this turn's prompt and a reply of the
            # length this turn asked for
            assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
            assert len(b.prompt) > len(a.prompt) + a.max_new
            assert b.due > a.due
    # popular prefixes are picked more often (Zipf)
    counts = np.bincount([r.prefix_id for r in t.requests])
    assert counts.argmax() == 0


def test_the_generator_takes_nothing_from_the_program():
    """Requests are a function of the mix, the seed and the window only:
    the generator is given no engine and reads no output."""
    import inspect
    params = inspect.signature(tr.generate).parameters
    assert list(params) == ["mix", "seed", "seconds", "vocab", "max_len",
                            "slots"]


def test_stratified_quantiles_and_zipf_counts():
    v = tr.stratified({"dist": "uniform", "min": 0, "max": 100}, 4)
    assert v.tolist() == [12, 38, 62, 88]
    ln = tr.stratified({"dist": "lognormal", "median": 128, "sigma": 0.6,
                        "min": 16, "max": 512}, 101)
    assert abs(ln[50] - 128) <= 2 and ln.min() >= 16 and ln.max() <= 512
    c = tr.zipf_counts(8, 1.1, 100)
    assert c.sum() == 100 and list(c) == sorted(c, reverse=True)
