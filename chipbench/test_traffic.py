"""The traffic generator: seeded determinism, the same work for every
seed, exact prompt sizes, and band shares of the scaled CDF."""
import math

import pytest

from chipbench import traffic as T

SEED = 2 ** 33 + 17


def mix(name, **kw):
    m = T.load(name)
    m.update(kw)
    return m


@pytest.mark.parametrize("name", ["agent-heavy.r1", "azure"])
def test_same_seed_same_schedule(name):
    m = mix(name, rate_per_s=2.0, warmup_s=2.0)
    a, b = T.schedule(m, SEED, 5), T.schedule(m, SEED, 5)
    assert [(r.due, r.l_in, r.l_out, r.category, r.prompt) for r in a] == \
        [(r.due, r.l_in, r.l_out, r.category, r.prompt) for r in b]


@pytest.mark.parametrize("name", ["agent-heavy.r1", "azure"])
def test_every_seed_gets_the_same_work(name):
    m = mix(name, rate_per_s=3.0, warmup_s=4.0)
    a, b = T.schedule(m, 1, 10), T.schedule(m, SEED, 10)
    for inside in (False, True):
        ka = sorted((r.l_in, r.l_out, r.category) for r in a
                    if r.in_window == inside)
        kb = sorted((r.l_in, r.l_out, r.category) for r in b
                    if r.in_window == inside)
        assert ka == kb
    assert [r.prompt for r in a] != [r.prompt for r in b]


@pytest.mark.parametrize("name", ["agent-heavy.r1", "azure"])
def test_a_fixed_order_is_the_same_for_every_seed(name):
    m = mix(name, rate_per_s=3.0, warmup_s=4.0, order="fixed")
    a, b = T.schedule(m, 1, 10), T.schedule(m, SEED, 10)
    assert [(r.due, r.l_in, r.l_out, r.category, r.in_window) for r in a] \
        == [(r.due, r.l_in, r.l_out, r.category, r.in_window) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    seeded = T.schedule(mix(name, rate_per_s=3.0, warmup_s=4.0,
                            order="seed"), 1, 10)
    assert [r.l_in for r in seeded] != [r.l_in for r in a]


def test_an_unknown_order_is_an_error():
    with pytest.raises(ValueError):
        T.schedule(mix("azure", rate_per_s=1.0, warmup_s=1.0,
                       order="sorted"), SEED, 2)


@pytest.mark.parametrize("name", ["agent-heavy.r1", "azure"])
def test_prompts_are_exactly_l_in_tokens_of_sentences(name):
    m = mix(name, rate_per_s=2.0, warmup_s=1.0)
    for r in T.schedule(m, SEED, 5):
        assert len(r.prompt.encode()) == 4 * r.l_in
        assert r.prompt.endswith(".")
        assert r.l_in + r.l_out <= m["max_total"] * m["scale"]


def test_arrivals_fill_the_segment_at_the_rate():
    m = mix("azure", rate_per_s=8.0, warmup_s=5.0)
    reqs = T.schedule(m, SEED, 20)
    win = [r.due for r in reqs if r.in_window]
    assert len(win) == 160
    assert min(win) >= 5.0 and max(win) < 25.0
    assert win == sorted(win)


@pytest.mark.parametrize("name", ["agent-heavy.r1", "azure"])
def test_band_shares_follow_the_scaled_cdf(name):
    m = mix(name, rate_per_s=50.0, warmup_s=0.0)
    reqs = T.schedule(m, SEED, 100)             # 5000 requests
    cdf = T.CDF(m["anchors"])
    b, g = m["borderline"]["b"], m["borderline"]["gamma"]
    top = float(cdf.cdf(m["max_total"]))
    want_short = float(cdf.cdf(b)) / top
    want_border = float(cdf.cdf(g * b) - cdf.cdf(b)) / top
    got = T.bands(reqs, int(b * m["scale"]), g)
    n = len(reqs)
    for got_v, want in ((got["short"], want_short),
                        (got["borderline"], want_border)):
        se = math.sqrt(max(want * (1 - want), 1e-4) / n)
        assert abs(got_v - want) < 4 * se + 2.0 / n


def test_borderline_code_share():
    m = mix("agent-heavy.r1", rate_per_s=50.0, warmup_s=0.0)
    reqs = T.schedule(m, SEED, 100)
    bl = m["borderline"]
    edge = [r for r in reqs
            if bl["b"] < r.l_total_unscaled <= bl["gamma"] * bl["b"]]
    code = sum(r.category == "code" for r in edge) / len(edge)
    assert code == pytest.approx(bl["code_frac"], abs=1.0 / len(edge))


def test_unknown_mix_is_an_error():
    with pytest.raises(FileNotFoundError):
        T.load("no-such-mix")


def test_a_cell_mix_sends_its_base_lengths_at_its_own_rate():
    base, cell = T.load("agent-heavy"), T.load("agent-heavy.r1")
    assert "rate_per_s" not in base and "base" not in cell
    assert cell["rate_per_s"] > 0
    for k in ("anchors", "lout", "borderline", "scale", "max_total"):
        assert cell[k] == base[k]
