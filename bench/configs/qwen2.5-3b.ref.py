"""Plain reference of Qwen2.5-3B (``Qwen2ForCausalLM``), in float32 at the
highest matmul precision, and the benchmark's own random weights for it.
Imports nothing of the program.

The forward pass follows the published architecture: token embedding,
then per layer RMSNorm, q/k/v projections with bias, rotary embedding
(the halves of each head rotated, base ``rope_theta``), causal grouped-
query attention (query head h reads key/value head h // (H / H_kv)), an
output projection without bias and a residual; RMSNorm, a SiLU-gated MLP
and a residual; a final RMSNorm and the tied embedding as output head.

``quantized=True`` computes every matmul from float8 (e4m3) operands,
scaled per output column for weights and per row for activations: the
precision below the bf16 the configuration states, as a control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def dims(cfg: dict) -> dict:
    return dict(L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], ff=cfg["intermediate_size"],
                V=cfg["vocab_size"], eps=cfg["rms_norm_eps"],
                theta=cfg["rope_theta"])


@functools.partial(jax.jit, static_argnames=("shape",))
def _weights(key, shape: tuple):
    """bf16 weights in the layout the serving engine takes: one jit on the
    device, each leaf drawn in f32 and rounded once."""
    L, d, H, Hkv, hd, ff, V = shape
    ks = iter(jax.random.split(key, 16))

    def normal(shp, std, mean=0.0):
        x = jax.random.normal(next(ks), shp, jnp.float32) * std + mean
        return x.astype(jnp.bfloat16)

    attn = dict(
        wq=normal((L, d, H * hd), 1 / math.sqrt(d)),
        wk=normal((L, d, Hkv * hd), 1 / math.sqrt(d)),
        wv=normal((L, d, Hkv * hd), 1 / math.sqrt(d)),
        wo=normal((L, H * hd, d), 1 / math.sqrt(2 * L * H * hd)),
        bq=normal((L, H * hd), 0.1),
        bk=normal((L, Hkv * hd), 0.1),
        bv=normal((L, Hkv * hd), 0.1),
    )
    mlp = dict(
        w_up=normal((L, d, ff), 1 / math.sqrt(d)),
        w_gate=normal((L, d, ff), 1 / math.sqrt(d)),
        w_down=normal((L, ff, d), 1 / math.sqrt(2 * L * ff)),
    )
    layers = dict(ln1=normal((L, d), 0.1, 1.0), ln2=normal((L, d), 0.1, 1.0),
                  attn=attn, ff=mlp)
    return dict(embed=normal((V, d), 0.02), ln_f=normal((d,), 0.1, 1.0),
                layers=layers)


def init_weights(cfg: dict, seed: int):
    """The weights of one run, from its seed."""
    k = dims(cfg)
    s = int(seed) % (1 << 64)
    key = jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF),
                             (s >> 31) & 0xFFFFFFFF)
    return _weights(key, (k["L"], k["d"], k["H"], k["Hkv"], k["hd"], k["ff"],
                          k["V"]))


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]          # (S, hd/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def make_logits_fn(cfg: dict, *, quantized: bool = False):
    """``fn(weights, tokens (S,), positions (K,)) -> (K, V)`` float32
    logits at ``positions`` of the causal forward pass over ``tokens``."""
    k = dims(cfg)
    H, Hkv, hd, eps = k["H"], k["Hkv"], k["hd"], k["eps"]
    group = H // Hkv

    def mm(a, b):
        if quantized:
            a, b = _q8(a, -1), _q8(b, 0)
        return jnp.matmul(a, b, precision=HIGHEST)

    def attend(q, kk, v):
        # q: (S, H, hd); kk, v: (S, Hkv, hd)
        kk = jnp.repeat(kk, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        if quantized:
            q, kk, v = _q8(q, -1), _q8(kk, -1), _q8(v, 0)
        s = jnp.einsum("qhd,khd->hqk", q, kk, precision=HIGHEST)
        s = s / math.sqrt(hd)
        n = q.shape[0]
        causal = np.tril(np.ones((n, n), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quantized:
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        a, f = p["attn"], p["ff"]
        n = x.shape[0]
        pos = jnp.arange(n)
        h = _rms(x, p["ln1"], eps)
        q = (mm(h, a["wq"]) + a["bq"]).reshape(n, H, hd)
        kk = (mm(h, a["wk"]) + a["bk"]).reshape(n, Hkv, hd)
        v = (mm(h, a["wv"]) + a["bv"]).reshape(n, Hkv, hd)
        o = attend(_rope(q, pos, k["theta"]), _rope(kk, pos, k["theta"]), v)
        x = x + mm(o.reshape(n, H * hd), a["wo"])
        h = _rms(x, p["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                   f["w_down"])
        return x, None

    @jax.jit
    def logits(w, tokens, positions):
        x = w["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = _rms(x[positions], w["ln_f"].astype(jnp.float32), eps)
        return mm(x, w["embed"].astype(jnp.float32).T)

    return logits


def served_gaps(logits_fn, weights, samples, *, pad_to: int, k_max: int,
                control_fn=None):
    """For each sampled request ``(prompt, served)``: the reference's
    logits at every served position, and by how much the served token's
    logit lies below the reference's best there. With ``control_fn``,
    also the gap of the token the control puts first, at the same
    positions. Returns ``(served_gaps, control_gaps)`` as flat arrays."""
    out, ctl = [], []
    for prompt, served in samples:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        if seq.size > pad_to or served.size > k_max:
            raise ValueError(f"sample of {seq.size} tokens and "
                             f"{served.size} served exceeds the reference's "
                             f"shape ({pad_to}, {k_max})")
        tokens = np.zeros(pad_to, np.int32)
        tokens[:seq.size] = seq
        pos = np.full(k_max, prompt.size - 1, np.int32)
        pos[:served.size] = prompt.size - 1 + np.arange(served.size)
        ref = np.asarray(logits_fn(weights, tokens, pos))[:served.size]
        best = ref.max(axis=-1)
        out.append(best - ref[np.arange(served.size), served])
        if control_fn is not None:
            c = np.asarray(control_fn(weights, tokens, pos))[:served.size]
            ctl.append(best - ref[np.arange(served.size), c.argmax(-1)])
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)
    return cat(out), cat(ctl)
