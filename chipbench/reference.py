"""Plain reference for the served path, independent of the program.

* ``tokenize``: the 4-bytes-per-token chunk tokenizer the gateway
  states (ids = little-endian chunk value mod (V - 1) + 1).
* ``expected_pools``: the FleetOpt routing rule for a two-pool plan:
  L_total = prompt tokens + max_tokens; at most B goes short; in
  (B, gamma * B] a prose or rag prompt is compressed into the short pool
  (unless its must-keep sentences overflow the budget) and any other
  goes long; above gamma * B goes long. The router
  estimates prompt tokens from bytes over a learned bytes-per-token
  ratio that can sit a little under 4, so within ``margin`` tokens of
  an edge both sides are accepted.
* ``compression_faults``: what a C&R-compressed prompt must be: whole
  sentences of the original in their order, the first three and last
  two kept, at most B - max_tokens tokens, and served as exactly the
  tokens of that text.
* The blocks a model module's ``Forward`` (``models/<name>.py``) is
  built from, in float32 at ``highest`` matmul precision: ``rms``
  (RMSNorm with a scale), ``rope`` (rotary embedding on each head's two
  halves), ``mm`` (a projection; with ``int8=True`` int8 inputs,
  per-column weight scales and per-row activation scales, int32
  accumulation: the control), ``gqa_attention`` (projections, rotary
  embedding and causal attention with KV heads shared by groups of
  query heads, in blocks of query rows, optionally over a window of
  keys), ``head_logits`` (final RMSNorm and an untied head in blocks of
  the vocabulary), ``forward_logits`` (the sequence padded to a bucket,
  the layers one by one, the head on the compared rows only) and
  ``LayerwiseForward``, which composes them around a module's layer.

The engine feeds the last prompt token once more at position n before
its first output, so a request of prompt p (n tokens) that served
g_1..g_m is the sequence p + [p[-1]] + g_1..g_{m-1}, and the logits at
positions n .. n+m-1 are the ones that chose g_1..g_m.
"""
from __future__ import annotations

import math
import re
from typing import Callable, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

BYTES_PER_TOKEN = 4
COMPRESSIBLE = ("prose", "rag")
PRIMACY, RECENCY = 3, 2
QUERY_BLOCK = 256
VOCAB_BLOCK = 16384
SEQ_BUCKETS = (1024, 2048, 4096, 8192, 12288, 16384, 20480, 24576, 32768)


def tokenize(text: str, vocab: int) -> List[int]:
    data = text.encode("utf-8")
    ids = [int.from_bytes(data[i:i + BYTES_PER_TOKEN], "little")
           % (vocab - 1) + 1 for i in range(0, len(data), BYTES_PER_TOKEN)]
    return ids or [1]


def expected_pools(prompt: str, max_tokens: int, category: str,
                   boundary: int, gamma: float, margin: int) -> Set[tuple]:
    """Allowed (pool, compressed) outcomes for one request. Compression
    into the short pool fails, and the request goes long, when the
    sentences C&R must keep (the first three and the last two) do not
    fit the budget B - max_tokens; within a few tokens of that edge
    either outcome stands."""
    prompt_tokens = math.ceil(len(prompt.encode()) / BYTES_PER_TOKEN)
    budget = boundary - max_tokens
    sents = sentences(prompt)
    keep = sents[:PRIMACY] + sents[PRIMACY:][-RECENCY:]
    must = math.ceil(len(" ".join(keep).encode()) / BYTES_PER_TOKEN)
    out = set()
    for lt in (prompt_tokens + max_tokens,
               prompt_tokens + max_tokens + margin):
        if lt <= boundary:
            out.add(("short", False))
        elif lt <= gamma * boundary and category in COMPRESSIBLE \
                and budget > 0:
            if must <= budget + 3:
                out.add(("short", True))
            if must >= budget - 3:
                out.add(("long", False))
        else:
            out.add(("long", False))
    return out


def sentences(text: str) -> List[str]:
    return [s for s in re.split(r"(?<=\.)\s+", text) if s]


def compression_faults(prompt: str, compressed: str, served: Sequence[int],
                       budget: int, vocab: int) -> List[str]:
    faults = []
    orig = sentences(prompt)
    kept = sentences(compressed)
    j = 0
    idx = []
    for s in kept:
        while j < len(orig) and orig[j] != s:
            j += 1
        if j == len(orig):
            faults.append("kept text is not whole sentences in order")
            break
        idx.append(j)
        j += 1
    n = len(orig)
    must = set(range(min(PRIMACY, n))) | set(range(max(0, n - RECENCY), n))
    if not must <= set(idx):
        faults.append("first three or last two sentences dropped")
    if math.ceil(len(compressed.encode()) / BYTES_PER_TOKEN) > budget:
        faults.append("compressed prompt over its budget")
    if list(served) != tokenize(compressed, vocab):
        faults.append("served tokens are not the compressed text")
    return faults


def _bucket(n: int, buckets=SEQ_BUCKETS) -> int:
    for b in buckets:
        if b >= n:
            return b
    return int(math.ceil(n / buckets[-1]) * buckets[-1])


def _q8(x, axis):
    """Symmetric int8 quantization along ``axis`` -> (int8, scale)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def mm(x, w, int8: bool):
    if not int8:
        return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    xq, sx = _q8(x, axis=-1)
    wq, sw = _q8(w, axis=0)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def gqa_attention(h, w, cfg: dict, n_real, int8: bool,
                  window: Optional[int] = None):
    """Attention of a normed (t, d) input with weights ``wq wk wv wo``:
    rotary embedding at positions 0 .. t-1, each query row attending
    the keys at or before it and before ``n_real`` (the padding after
    the sequence is never a key), with ``window`` set only the last
    ``window`` of them, ``num_key_value_heads`` KV heads each shared by
    a group of query heads; in blocks of QUERY_BLOCK query rows.
    Returns the output projection, (t, d)."""
    t = h.shape[0]
    h_, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = h_ // kv
    theta = cfg["rope_theta"]
    pos = jnp.arange(t)
    q = rope(mm(h, w["wq"], int8).reshape(t, h_, hd), pos, theta)
    k = rope(mm(h, w["wk"], int8).reshape(t, kv, hd), pos, theta)
    v = mm(h, w["wv"], int8).reshape(t, kv, hd)
    qb = q.reshape(t // QUERY_BLOCK, QUERY_BLOCK, kv, g, hd)
    keys = jnp.arange(t)

    def block(args):
        bi, qi = args
        s = jnp.einsum("bkgd,tkd->kgbt", qi, k,
                       precision=jax.lax.Precision.HIGHEST)
        s = s / math.sqrt(hd)
        rows = bi * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        ok = (keys[None, :] <= rows[:, None]) & (keys[None, :] < n_real)
        if window is not None:
            ok = ok & (keys[None, :] > rows[:, None] - window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgbt,tkd->bkgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, (jnp.arange(t // QUERY_BLOCK), qb))
    return mm(o.reshape(t, h_ * hd), w["wo"], int8)


def head_logits(x, g, head, eps, int8: bool):
    """Final RMSNorm (float32 scale ``g``) and the untied head (d, V) in
    its served dtype, in blocks of VOCAB_BLOCK columns, each cast to
    float32 as it is used."""
    x = rms(x, g, eps)
    v = head.shape[1]
    nblk = -(-v // VOCAB_BLOCK)
    pad = nblk * VOCAB_BLOCK - v
    head = jnp.pad(head, ((0, 0), (0, pad)))
    blocks = head.reshape(head.shape[0], nblk, VOCAB_BLOCK)

    def one(wb):
        return mm(x, wb.astype(jnp.float32), int8)
    out = jax.lax.map(one, jnp.moveaxis(blocks, 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)[:, :v]


def forward_logits(tokens: Sequence[int], first: int,
                   embed: Callable, layer: Callable, num_layers: int,
                   head: Callable) -> np.ndarray:
    """Logits (float32) at positions first .. len(tokens)-1, at
    ``highest`` matmul precision: the tokens padded to a bucket,
    ``embed(ids)``, ``layer(i, x, n)`` for i in 0 .. num_layers-1 (n,
    an int32, is the length before the padding), then ``head(rows)`` on
    the compared rows, padded to a bucket of their own."""
    n = len(tokens)
    t = _bucket(n)
    toks = np.zeros(t, np.int32)
    toks[:n] = tokens
    with jax.default_matmul_precision("highest"):
        x = embed(jnp.asarray(toks))
        for i in range(num_layers):
            x = layer(i, x, jnp.int32(n))
        m = n - first
        gb = _bucket(m, (64, 128, 256, 512, 1024, 2048, 4096, 8192,
                         16384))
        sel = jnp.zeros((gb, x.shape[1]), jnp.float32)
        sel = sel.at[:m].set(x[first:n])
        out = head(sel)
    return np.asarray(out[:m])


class LayerwiseForward:
    """Reference logits for one configuration and seed, from a model
    module's weights and its layer: ``layer_params(cfg, seed_key, i,
    dtype)`` and ``top_params(cfg, seed_key, leaf, dtype)`` draw the
    leaves in the served dtype (the top-level ones are ``embed``,
    ``final_ln`` and an untied ``lm_head``), and ``block(cfg, w, x,
    n_real, int8)`` is one layer on the float32 leaves ``w``. The
    embedding, a layer and the head are one jitted call each, every
    layer's weights drawn inside its call; ``int8=True`` is the
    control."""

    def __init__(self, cfg: dict, seed: int, int8: bool,
                 layer_params: Callable, top_params: Callable,
                 block: Callable):
        self.cfg, self.int8 = cfg, int8
        self.seed_key = W.root_key(seed)
        dtype = jnp.dtype(cfg["dtype"])

        def embed(seed_key, tokens):
            emb = top_params(cfg, seed_key, "embed", dtype)
            return emb[tokens].astype(jnp.float32)

        def layer(seed_key, i, x, n_real):
            w = jax.tree.map(lambda a: a.astype(jnp.float32),
                             layer_params(cfg, seed_key, i, dtype))
            return block(cfg, w, x, n_real, int8)

        def head(seed_key, x):
            g = top_params(cfg, seed_key, "final_ln",
                           dtype).astype(jnp.float32)
            w = top_params(cfg, seed_key, "lm_head", dtype)
            return head_logits(x, g, w, cfg["norm_eps"], int8)
        self._embed, self._layer, self._head = (
            jax.jit(embed), jax.jit(layer), jax.jit(head))

    def logits(self, tokens, first: int):
        """Logits (float32) at positions first .. len(tokens)-1."""
        key = self.seed_key
        return forward_logits(
            tokens, first, lambda ids: self._embed(key, ids),
            lambda i, x, n: self._layer(key, jnp.int32(i), x, n),
            self.cfg["num_hidden_layers"], lambda x: self._head(key, x))


def served_sequence(prompt_ids: Sequence[int], served: Sequence[int]):
    """(tokens fed, first compared position) for a served request."""
    ids = list(prompt_ids)
    return ids + [ids[-1]] + list(served[:-1]), len(ids)


def gaps(ref_logits: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at its position."""
    ref = np.asarray(ref_logits, np.float32)
    ch = np.asarray(chosen)
    return ref.max(-1) - ref[np.arange(len(ch)), ch]


def sample_requests(done: Dict[int, dict], seed: int, min_tokens: int,
                    max_requests: int) -> List[int]:
    """Requests to compare: the longest (prompt + served tokens), then
    the longest of each pool and a compressed one where there are any,
    then others in an order drawn from the seed until ``min_tokens``
    served tokens are covered, ``max_requests`` are picked, or none is
    left. A larger ``max_requests`` changes no pick that a smaller one
    already carried to ``min_tokens``."""
    if not done:
        return []
    def size(k):
        return len(done[k]["prompt_ids"]) + len(done[k]["served"])
    keys = sorted(done)
    pick = [max(keys, key=size)]
    for pool in sorted({done[k]["pool"] for k in keys}):
        cand = [k for k in keys if done[k]["pool"] == pool]
        best = max(cand, key=size)
        if best not in pick:
            pick.append(best)
    comp = [k for k in keys if done[k]["compressed"]]
    if comp and not any(done[k]["compressed"] for k in pick):
        pick.append(max(comp, key=size))
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
    rest = [k for k in rng.permutation(keys).tolist() if k not in pick]
    while rest and len(pick) < max_requests and \
            sum(len(done[k]["served"]) for k in pick) < min_tokens:
        pick.append(rest.pop(0))
    return pick
