"""The one traffic generator. A traffic mix is a JSON file of
parameters under ``chipbench/traffic/``; this module turns it and a
seed into a request schedule.

Lengths follow a piecewise log-linear CDF of L_total (prompt + output
tokens) and the output-length model L_out = clip(a * L_total^q * eps),
eps lognormal(sigma): both copied, with their anchors, from the
program's workload models (``repro.core.workload``) into the mix files.
Categories follow the mix's shares, with the mix's code fraction inside
the borderline band (the C&R content gate compresses prose and rag
only). Lengths are then scaled by the mix's ``scale`` (rounded up, the
output floored at the model's minimum), so every band keeps its share.

The work does not depend on the seed. Each segment of the schedule
(the warm-up, then the measured window) holds a fixed, stratified set
of sizes, output lengths, categories and Poisson gaps. The mix's
``order`` says who orders them: ``"seed"`` (the default) lets the seed
shuffle them, so two seeds differ in the order of the same requests
and not in how much there is to do; ``"fixed"`` orders them by a fixed
generator, the same for every seed. A cell far above its knee, where
requests outlive the window, wants ``"fixed"``: there the order of
arrival decides which requests hold the slots, and so the batch the
window sees. Either way the seed draws the prompt text.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
BYTES_PER_TOKEN = 4


@dataclasses.dataclass
class Req:
    idx: int
    due: float            # seconds after the first send
    l_in: int             # prompt tokens, scaled
    l_out: int            # max_tokens, scaled
    category: str
    in_window: bool
    l_total_unscaled: int
    prompt: str = ""


def load(name: str) -> dict:
    """A mix by name. A cell's mix names the ``base`` mix whose lengths
    it sends and sets what the cell fixes: the rate, the warm-up and
    how long requests due in the window are followed."""
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if "base" in mix:
        mix = {**load(mix.pop("base")), **mix}
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


class CDF:
    """Piecewise log-linear CDF over token counts."""

    def __init__(self, anchors: Sequence[Sequence[float]]):
        xs = np.array([a[0] for a in anchors], np.float64)
        fs = np.array([a[1] for a in anchors], np.float64)
        if not (np.all(np.diff(xs) > 0) and np.all(np.diff(fs) >= 0)
                and fs[0] == 0.0 and fs[-1] == 1.0):
            raise ValueError("CDF anchors must rise from 0 to 1")
        self.log_x, self.f, self.xs = np.log(xs), fs, xs

    def cdf(self, x) -> np.ndarray:
        x = np.asarray(x, np.float64)
        return np.interp(np.log(np.maximum(x, self.xs[0])), self.log_x,
                         self.f)

    def quantile(self, p) -> np.ndarray:
        return np.exp(np.interp(np.asarray(p, np.float64), self.f,
                                self.log_x))


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _counts(shares: Dict[str, float], n: int) -> List[str]:
    """n labels in the given shares, by largest remainder."""
    names = list(shares)
    total = sum(shares.values())
    exact = [shares[k] / total * n for k in names]
    base = [math.floor(e) for e in exact]
    order = sorted(range(len(names)), key=lambda i: base[i] - exact[i])
    for i in order[: n - sum(base)]:
        base[i] += 1
    return [names[i] for i, c in enumerate(base) for _ in range(c)]


def segment(mix: dict, n: int, rng: np.random.Generator
            ) -> List[Tuple[int, int, str, int]]:
    """n requests of one segment: (l_in, l_out, category, l_total
    before scaling). The requests are fixed by the mix and n alone
    (stratified sizes, output-length noise and categories, paired by a
    fixed generator); ``rng`` only orders them."""
    fixed = np.random.default_rng(0)
    cdf = CDF(mix["anchors"])
    top = float(cdf.cdf(mix["max_total"]))
    l_total = np.maximum(np.round(cdf.quantile(_strata(n) * top)), 2.0)
    lo = mix["lout"]
    nd = statistics.NormalDist()
    z = fixed.permutation(np.array([nd.inv_cdf(u) for u in _strata(n)]))
    l_out = np.clip(np.round(lo["a"] * l_total ** lo["q"]
                             * np.exp(lo["sigma"] * z)), lo["min"], lo["max"])
    l_out = np.minimum(l_out, l_total - 1)
    l_in = l_total - l_out
    bl = mix["borderline"]
    border = (l_total > bl["b"]) & (l_total <= bl["gamma"] * bl["b"])
    cats = np.empty(n, object)
    plain = np.where(~border)[0]
    cats[plain] = fixed.permutation(_counts(mix["category_probs"],
                                            len(plain)))
    edge = np.where(border)[0]
    if len(edge):
        n_code = round(bl["code_frac"] * len(edge))
        rest = _counts(bl["noncode_split"], len(edge) - n_code)
        cats[edge] = fixed.permutation(["code"] * n_code + rest)
    s = mix["scale"]
    cap = math.floor(mix["max_total"] * s)
    out = []
    for i in rng.permutation(n):
        lo_ = max(math.ceil(l_out[i] * s), lo["min"])
        li = max(1, min(math.ceil(l_in[i] * s), cap - lo_))
        out.append((li, lo_, str(cats[i]), int(l_total[i])))
    return out


def arrivals(rate: float, start: float, length: float, n: int,
             rng: np.random.Generator) -> np.ndarray:
    """n Poisson arrival times in [start, start + length): stratified
    exponential gaps, shuffled, stretched to fill the segment."""
    gaps = rng.permutation(-np.log1p(-_strata(n)) / rate)
    total = gaps.sum()
    return start + length * (np.cumsum(gaps) - gaps) / total


def schedule(mix: dict, seed: int, seconds: float) -> List[Req]:
    """The whole run's requests: the warm-up segment, then the measured
    window of ``seconds``, at the mix's fixed rate."""
    rate, warm = float(mix["rate_per_s"]), float(mix["warmup_s"])
    order = mix.get("order", "seed")
    if order not in ("seed", "fixed"):
        raise ValueError(f"order {order!r}: 'seed' or 'fixed'")
    rng = rng_for(seed if order == "seed" else 0, 1)
    reqs: List[Req] = []
    for start, length, inside in ((0.0, warm, False),
                                  (warm, float(seconds), True)):
        n = max(1, round(rate * length)) if length > 0 else 0
        if not n:
            continue
        times = arrivals(rate, start, length, n, rng)
        for t, (li, lo, cat, lt) in zip(times, segment(mix, n, rng)):
            reqs.append(Req(len(reqs), float(t), li, lo, cat, inside, lt))
    words = lexicon(rng_for(seed, 2))
    text_rng = rng_for(seed, 3)
    for r in reqs:
        r.prompt = prompt_text(r.l_in, words, text_rng)
    return reqs


def lexicon(rng: np.random.Generator, n: int = 3000) -> List[str]:
    syll = ["ka", "lo", "mi", "ren", "tor", "va", "sel", "dun", "pri",
            "ost", "mel", "fa", "zu", "gri", "hem", "no", "tas", "bel"]
    out, seen = [], set()
    while len(out) < n:
        k = int(rng.integers(1, 4))
        w = "".join(syll[int(i)] for i in rng.integers(0, len(syll), k))
        if w not in seen:
            seen.add(w)
            out.append(w)
    return out


def prompt_text(l_in: int, words: List[str],
                rng: np.random.Generator) -> str:
    """ASCII prose of exactly 4 * l_in bytes (so exactly l_in tokens
    under a 4-bytes-per-token tokenizer), made of sentences that end in
    '. ' for the C&R sentence splitter to rank."""
    target = BYTES_PER_TOKEN * l_in
    parts, size, k = [], 0, 0
    while size < target:
        n = int(rng.integers(8, 17))
        idx = rng.integers(0, len(words), n)
        sent = f"Item {k} " + " ".join(words[int(i)] for i in idx) + "."
        parts.append(sent)
        size += len(sent) + 1
        k += 1
    text = " ".join(parts)[: max(target - 1, 0)]
    if text.endswith(" "):
        text = text[:-1] + "a"
    return text + "."


def bands(reqs: Sequence[Req], boundary: int, gamma: float) -> Dict[str, float]:
    """Shares of requests that the router's rule puts short, borderline
    (C&R candidates) and long, on scaled lengths."""
    n = max(1, len(reqs))
    short = sum(r.l_in + r.l_out <= boundary for r in reqs)
    border = sum(boundary < r.l_in + r.l_out <= gamma * boundary
                 for r in reqs)
    return {"short": short / n, "borderline": border / n,
            "long": (len(reqs) - short - border) / n}
