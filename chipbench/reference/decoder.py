"""Plain float32 reference of the SALO-masked dense decoder.

Independent of the program: it imports nothing from it and takes only the
benchmark's own weights (``chipbench.model.make_params``). Layer by layer
in straightforward ``jax.numpy``, every matrix product at
``precision=HIGHEST``:

  x = embed[tok] * sqrt(d)
  per layer: h = rms(x) * (1 + s1);  q, k, v = h Wq, h Wk, h Wv (RoPE on q, k)
             x += softmax(q k^T / sqrt(hd) + mask) v Wo      (GQA groups)
             h = rms(x) * (1 + s2);  x += (silu(h Wg) * (h Win)) Wout
  logits = (rms(x) * (1 + sf)) E^T        (E = embed, or lm_head if untied)

The mask is causal, over the last ``window`` keys plus the first ``sinks``
keys. Attention runs in blocks of ``BLOCK`` queries against the keys that
block can see, so a 32k-token sequence fits beside its weights.

``mode="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 with one scale per tensor (amax / 448), the step
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512          # queries per attention block
BUCKET = 2048        # sequence lengths are padded up to a multiple of this
HEAD_ROWS = 128      # logits rows per head call
HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with one scale per tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, mode):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _layer(x, lp, m, mode):
    """One decoder layer over the whole (padded) sequence x: (N, d)."""
    N = x.shape[0]
    H, Hkv, hd, W, g = m["H"], m["Hkv"], m["hd"], m["window"], m["sinks"]
    rep = H // Hkv
    pos = jnp.arange(N)
    h = _rms(x, lp["ln1"], m["eps"])
    q = _rope(_ein("nd,de->ne", h, lp["wq"], mode).reshape(N, H, hd),
              pos, m["theta"])
    k = _rope(_ein("nd,de->ne", h, lp["wk"], mode).reshape(N, Hkv, hd),
              pos, m["theta"])
    v = _ein("nd,de->ne", h, lp["wv"], mode).reshape(N, Hkv, hd)
    kpad = jnp.concatenate([jnp.zeros((W, Hkv, hd)), k])
    vpad = jnp.concatenate([jnp.zeros((W, Hkv, hd)), v])

    def block(s):
        qb = jax.lax.dynamic_slice_in_dim(q, s, BLOCK)       # (B, H, hd)
        qpos = s + jnp.arange(BLOCK)
        kw = jax.lax.dynamic_slice_in_dim(kpad, s, W + BLOCK)
        vw = jax.lax.dynamic_slice_in_dim(vpad, s, W + BLOCK)
        kpos_w = s - W + jnp.arange(W + BLOCK)
        keys = jnp.concatenate([k[:g], kw])
        vals = jnp.concatenate([v[:g], vw])
        kpos = jnp.concatenate([jnp.arange(g), kpos_w])
        in_window = (kpos[None] >= qpos[:, None] - (W - 1)) \
            & (jnp.arange(g + W + BLOCK) >= g)[None] & (kpos[None] >= 0)
        is_sink = (jnp.arange(g + W + BLOCK) < g)[None] \
            & (kpos[None] < qpos[:, None] - (W - 1))
        mask = (in_window | is_sink) & (kpos[None] <= qpos[:, None])
        qg = qb.reshape(BLOCK, Hkv, rep, hd)
        sc = _ein("bgrd,kgd->grbk", qg, keys, mode) * hd ** -0.5
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = _ein("grbk,kgd->bgrd", p, vals, mode)
        return o.reshape(BLOCK, H * hd)

    att = jax.lax.map(block, jnp.arange(0, N, BLOCK))
    x = x + _ein("ne,ed->nd", att.reshape(N, H * hd), lp["wo"], mode)
    h = _rms(x, lp["ln2"], m["eps"])
    gate = jax.nn.silu(_ein("nd,df->nf", h, lp["w_gate"], mode))
    up = _ein("nd,df->nf", h, lp["w_in"], mode)
    return x + _ein("nf,fd->nd", gate * up, lp["w_out"], mode)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _head(x, ln_f, table, m, mode):
    return _ein("nd,vd->nv", _rms(x, ln_f, m["eps"]), table, mode)


class _M(dict):
    """Hashable dims, so they can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(m: dict, params, tokens: np.ndarray, rows: np.ndarray,
              mode: str = "f32") -> np.ndarray:
    """float32 logits (len(rows), V) of the next token after each position
    in ``rows`` of the sequence ``tokens``."""
    m = _M(m)
    N = len(tokens)
    Np = -(-N // BUCKET) * BUCKET
    tok = np.zeros(Np, np.int32)
    tok[:N] = tokens
    x = jnp.take(params["embed"]["w"], jnp.asarray(tok), axis=0
                 ).astype(jnp.float32) * float(np.sqrt(m["d"]))
    seg = params["seg0_attn_mlp"]
    for i in range(m["L"]):
        lp = {"ln1": seg["ln1"]["scale"][i], "ln2": seg["ln2"]["scale"][i],
              **{k: a[i] for k, a in seg["attn"].items()},
              **{k: a[i] for k, a in seg["mlp"].items()}}
        x = _layer(x, lp, m, mode)
    table = params["embed"]["w"] if m["tied"] else params["lm_head"]["w"]
    out = []
    for s in range(0, len(rows), HEAD_ROWS):
        r = np.zeros(HEAD_ROWS, np.int64)
        chunk = rows[s:s + HEAD_ROWS]
        r[:len(chunk)] = chunk
        lg = _head(x[jnp.asarray(r)], params["ln_f"]["scale"], table, m,
                   mode)
        out.append(np.asarray(lg)[:len(chunk)])
    return np.concatenate(out)


def served_gaps(m: dict, params, prompt: np.ndarray, out: np.ndarray,
                control: bool = False) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position. With ``control``, the token judged
    is the one the fp8 control puts first, on the same prompt and tokens."""
    tokens = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(tokens))
    ref = logits_at(m, params, tokens, rows)
    pick = np.asarray(out)
    if control:
        pick = np.argmax(logits_at(m, params, tokens, rows, "fp8"), -1)
    return ref.max(-1) - ref[np.arange(len(rows)), pick]
