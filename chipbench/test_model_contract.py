"""The model-module contract: a configuration names its module, the
harness refuses one that is missing or incomplete, and the dense
squared-ReLU module gives, bit for bit, the weights, reference logits
and per-layer counts that the harness computed before the block moved
out of it. ``Before`` below is that code, kept unchanged as the
oracle."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness as H

SEED = 2 ** 33 + 11
CONFIGS = ("minitron-8b", "nemotron-4-15b")


class Before:
    """The harness's weights, reference and counts for the dense
    squared-ReLU block as they stood when the block moved into
    ``models/dense_relu2.py``."""
    LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln2", "up", "down")
    TOP = ("embed", "final_ln", "lm_head")
    QUERY_BLOCK, VOCAB_BLOCK = 256, 16384

    @staticmethod
    def shape(cfg, leaf):
        d, h, kv, hd, ff, v = (cfg["hidden_size"],
                               cfg["num_attention_heads"],
                               cfg["num_key_value_heads"], cfg["head_dim"],
                               cfg["intermediate_size"], cfg["vocab_size"])
        return {"ln1": (d,), "ln2": (d,), "final_ln": (d,),
                "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
                "wo": (h * hd, d), "up": (d, ff), "down": (ff, d),
                "embed": (v, d), "lm_head": (d, v)}[leaf]

    @classmethod
    def leaf_key(cls, seed_key, leaf):
        return jax.random.fold_in(seed_key, (cls.TOP + cls.LAYER).index(leaf))

    @classmethod
    def draw(cls, cfg, leaf, key, dtype):
        shape = cls.shape(cfg, leaf)
        x = jax.random.normal(key, shape, jnp.float32)
        if leaf in ("ln1", "ln2", "final_ln"):
            x = 1.0 + jnp.round(jnp.clip(8.0 * x, -24.0, 24.0)) / 64.0
        elif leaf != "embed":
            x = x * 2.0 ** -round(math.log2(math.sqrt(shape[0])))
        return x.astype(dtype)

    @classmethod
    def layer(cls, cfg, seed_key, i, dtype):
        return {n: cls.draw(cfg, n, jax.random.fold_in(
            cls.leaf_key(seed_key, n), i), dtype) for n in cls.LAYER}

    @classmethod
    def program_params(cls, cfg, seed):
        dtype = jnp.dtype(cfg["dtype"])

        def make(seed_key):
            st = jax.vmap(lambda i: cls.layer(cfg, seed_key, i, dtype))(
                jnp.arange(cfg["num_hidden_layers"]))
            return {
                **{n: cls.draw(cfg, n, cls.leaf_key(seed_key, n), dtype)
                   for n in cls.TOP},
                "layers": {
                    "ln1": st["ln1"], "ln2": st["ln2"],
                    "attn": {k: st[k] for k in ("wq", "wk", "wv", "wo")},
                    "mlp": {"up": st["up"], "down": st["down"]}}}
        k = jax.random.key(int(seed) & 0xFFFFFFFF)
        k = jax.random.fold_in(k, (int(seed) >> 32) & 0x7FFFFFFF)
        return jax.jit(make)(k)

    @staticmethod
    def mm(x, w, int8):
        if not int8:
            return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)

        def q8(x, axis):
            amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
            scale = jnp.maximum(amax, 1e-30) / 127.0
            return jnp.clip(jnp.round(x / scale), -127,
                            127).astype(jnp.int8), scale
        xq, sx = q8(x, -1)
        wq, sw = q8(w, 0)
        acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sx * sw

    @staticmethod
    def rms(x, g, eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * g

    @staticmethod
    def rope(x, pos, theta):
        half = x.shape[-1] // 2
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                 / half))
        ang = pos[:, None, None].astype(jnp.float32) * freqs
        c, s = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)

    @classmethod
    def logits(cls, cfg, seed, tokens, first, int8):
        """The reference's ``Forward(cfg, seed, int8).logits``."""
        B, QB = cls, cls.QUERY_BLOCK
        dtype = jnp.dtype(cfg["dtype"])
        k = jax.random.key(int(seed) & 0xFFFFFFFF)
        seed_key = jax.random.fold_in(k, (int(seed) >> 32) & 0x7FFFFFFF)

        def layer_fn(seed_key, i, x, n_real):
            w = jax.tree.map(lambda a: a.astype(jnp.float32),
                             B.layer(cfg, seed_key, i, dtype))
            t = x.shape[0]
            h_, kv, hd = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
            g = h_ // kv
            eps, theta = cfg["norm_eps"], cfg["rope_theta"]
            pos = jnp.arange(t)
            h = B.rms(x, w["ln1"], eps)
            q = B.rope(B.mm(h, w["wq"], int8).reshape(t, h_, hd), pos,
                       theta)
            k = B.rope(B.mm(h, w["wk"], int8).reshape(t, kv, hd), pos,
                       theta)
            v = B.mm(h, w["wv"], int8).reshape(t, kv, hd)
            qb = q.reshape(t // QB, QB, kv, g, hd)
            keys = jnp.arange(t)

            def block(args):
                bi, qi = args
                s = jnp.einsum("bkgd,tkd->kgbt", qi, k,
                               precision=jax.lax.Precision.HIGHEST)
                s = s / math.sqrt(hd)
                rows = bi * QB + jnp.arange(QB)
                ok = (keys[None, :] <= rows[:, None]) & \
                    (keys[None, :] < n_real)
                s = jnp.where(ok[None, None], s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1)
                return jnp.einsum("kgbt,tkd->bkgd", p, v,
                                  precision=jax.lax.Precision.HIGHEST)

            o = jax.lax.map(block, (jnp.arange(t // QB), qb))
            x = x + B.mm(o.reshape(t, h_ * hd), w["wo"], int8)
            h = B.rms(x, w["ln2"], eps)
            u = jnp.square(jax.nn.relu(B.mm(h, w["up"], int8)))
            return x + B.mm(u, w["down"], int8)

        def embed_fn(seed_key, tokens):
            emb = B.draw(cfg, "embed", B.leaf_key(seed_key, "embed"), dtype)
            return emb[tokens].astype(jnp.float32)

        def head_fn(seed_key, x):
            g = B.draw(cfg, "final_ln", B.leaf_key(seed_key, "final_ln"),
                       dtype).astype(jnp.float32)
            head = B.draw(cfg, "lm_head", B.leaf_key(seed_key, "lm_head"),
                          dtype)
            x = B.rms(x, g, cfg["norm_eps"])
            v = head.shape[1]
            nblk = -(-v // B.VOCAB_BLOCK)
            head = jnp.pad(head, ((0, 0), (0, nblk * B.VOCAB_BLOCK - v)))
            blocks = head.reshape(head.shape[0], nblk, B.VOCAB_BLOCK)
            out = jax.lax.map(lambda wb: B.mm(x, wb.astype(jnp.float32),
                                              int8),
                              jnp.moveaxis(blocks, 1, 0))
            return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)[:, :v]

        def bucket(n, buckets):
            for b in buckets:
                if b >= n:
                    return b
            return int(math.ceil(n / buckets[-1]) * buckets[-1])

        layer_j, embed_j, head_j = (jax.jit(layer_fn), jax.jit(embed_fn),
                                    jax.jit(head_fn))
        n = len(tokens)
        toks = np.zeros(bucket(n, (1024, 2048, 4096)), np.int32)
        toks[:n] = tokens
        with jax.default_matmul_precision("highest"):
            x = embed_j(seed_key, jnp.asarray(toks))
            for i in range(cfg["num_hidden_layers"]):
                x = layer_j(seed_key, jnp.int32(i), x, jnp.int32(n))
            m = n - first
            gb = bucket(m, (64, 128, 256, 512, 1024))
            sel = jnp.zeros((gb, x.shape[1]), jnp.float32)
            sel = sel.at[:m].set(x[first:n])
            out = head_j(seed_key, sel)
        return np.asarray(out[:m])

    @staticmethod
    def step_mfu(rec):
        cfg = rec.cfg
        d, h, kv, hd, ff, L = (cfg["hidden_size"],
                               cfg["num_attention_heads"],
                               cfg["num_key_value_heads"], cfg["head_dim"],
                               cfg["intermediate_size"],
                               cfg["num_hidden_layers"])
        lin = 2.0 * (d * h * hd + 2 * d * kv * hd + h * hd * d
                     + 2 * d * ff) * L
        head = 2.0 * d * cfg["vocab_size"]
        per_ctx = 4.0 * h * hd * 1 * L
        steps = [s for s in rec.spans if s["name"].startswith("step.")]
        total = 0.0
        for s in steps:
            for start, n in s["prefill"]:
                total += n * lin + per_ctx * (n * start + n * (n + 1) / 2)
            for pos in s["decode"]:
                total += lin + head + per_ctx * (pos + 1)
        secs = sum(s["t1"] - s["t0"] for s in steps)
        return 100.0 * total / (secs * rec.peak["bf16_flops_per_s"])

    @staticmethod
    def paged_decode_roofline(rec):
        cfg = rec.cfg
        h, kv, hd, L = (cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"],
                        cfg["num_hidden_layers"])
        least = 0.0
        for s in rec.spans:
            if not s["name"].startswith("step.") or \
                    s["t0"] < rec.trace_t0 or s["t1"] > rec.trace_t1:
                continue
            for pos in s["decode"]:
                ctx = pos + 1
                f = 4.0 * h * hd * ctx * L
                b = (2.0 * ctx * kv * hd + 2.0 * h * hd) * 2 * L
                least += max(f / rec.peak["bf16_flops_per_s"],
                             b / rec.peak["hbm_bytes_per_s"])
        return 100.0 * least / rec.trace["kernel_s"]["paged_decode"]


def tiny(name="minitron-8b", dtype="bfloat16"):
    cfg = H.load_config(name)
    cfg.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=64, intermediate_size=512,
               vocab_size=20000, dtype=dtype)
    return cfg


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_program_params_are_bit_identical_to_before(dtype):
    cfg = tiny(dtype=dtype)
    new = H.load_model(cfg).program_params(cfg, SEED)
    old = Before.program_params(cfg, SEED)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", CONFIGS)
def test_full_size_tree_has_the_shapes_of_before(name):
    cfg = H.load_config(name)
    new = jax.eval_shape(lambda: H.load_model(cfg).program_params(cfg, 0))
    old = jax.eval_shape(lambda: Before.program_params(cfg, 0))
    assert jax.tree.structure(new) == jax.tree.structure(old)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(new)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(old)]


@pytest.mark.parametrize("int8", [False, True])
def test_reference_logits_are_bit_identical_to_before(int8):
    # two vocabulary blocks, a sequence past one query block, and the
    # compared rows starting mid-sequence
    cfg = tiny()
    toks = np.random.default_rng(2).integers(1, 20000, 300).tolist()
    new = H.load_model(cfg).Forward(cfg, SEED, int8=int8).logits(toks, 200)
    old = Before.logits(cfg, SEED, toks, 200, int8)
    assert new.shape == old.shape == (100, 20000)
    np.testing.assert_array_equal(new, old)


def record(name):
    """A traced window's record by hand: prefill chunks alone and fused
    with decode rows, decode steps of both pools, and one step that
    ends after the trace (the roofline leaves it out)."""
    cfg = H.load_config(name)
    rec = H.Record(cfg=cfg, peak=H.peaks_for("TPU v5 lite"),
                   model=H.load_model(cfg))
    rec.trace_t0, rec.trace_t1 = 10.0, 14.0
    rec.trace = {"kernel_s": {"paged_decode": 0.0123}}
    rec.spans = [
        {"name": "step.short", "t0": 10.1, "t1": 10.2,
         "prefill": [(0, 64), (64, 64)], "decode": []},
        {"name": "step.short", "t0": 10.3, "t1": 10.41,
         "prefill": [(4032, 64)], "decode": [5, 700, 4095]},
        {"name": "step.long", "t0": 11.0, "t1": 11.07, "prefill": [],
         "decode": [9000, 16383]},
        {"name": "gateway.flush", "t0": 11.1, "t1": 11.2},
        {"name": "step.short", "t0": 13.9, "t1": 14.2, "prefill": [],
         "decode": list(range(100, 116))},
    ]
    return rec


@pytest.mark.parametrize("name", CONFIGS)
def test_readers_read_what_they_read_before(name):
    rec = record(name)
    mfu = H.load_reader("step.mfu")(rec)
    roof = H.load_reader("paged_decode_roofline")(rec)
    assert mfu == Before.step_mfu(rec) and mfu > 0
    assert roof == Before.paged_decode_roofline(rec) and roof > 0


def test_a_configuration_without_a_model_is_an_error():
    cfg = H.load_config("minitron-8b")
    del cfg["model"]
    with pytest.raises(KeyError, match='"model"'):
        H.load_model(cfg)
    with pytest.raises(FileNotFoundError, match="no-such-model"):
        H.load_model(dict(cfg, model="no-such-model"))


def test_a_module_without_decode_kernel_cost_is_an_error(tmp_path,
                                                        monkeypatch):
    (tmp_path / "partial.py").write_text("".join(
        f"{n} = None\n" for n in H.MODEL_API if n != "decode_kernel_cost"))
    monkeypatch.setattr(H, "MODELS", tmp_path)
    cfg = dict(H.load_config("minitron-8b"), model="partial")
    with pytest.raises(AttributeError, match="lacks decode_kernel_cost$"):
        H.load_model(cfg)


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_a_complete_model(name):
    model = H.load_model(H.load_config(name))
    assert set(H.MODEL_API) <= set(vars(model))
    assert "paged_decode" in model.KERNELS
