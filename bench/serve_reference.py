"""The plain float32 reference that decides ``correct`` for serving cells of a
granite-moe decoder.  Nothing here imports the program.

The forward pass follows the published GraniteMoe description, read from the
configuration file's published keys: token embedding, then per layer a
pre-norm grouped-query attention block with rotary embeddings (half-split
rotation, ``rope_theta``) and a pre-norm mixture-of-experts block (softmax
router over ``num_local_experts``, the top ``num_experts_per_tok`` experts
with their probabilities renormalised, each expert a SwiGLU of width
``intermediate_size``), a final RMSNorm (``rms_norm_eps``) and the tied
embedding as the head.  Every matrix product runs in float32 at
``highest`` precision; there is no kernel, cache or batching, and routing
drops no token.  Departures, as the configuration's ``assumed`` says: the
published embedding, attention, residual and logits multipliers are left
out (the attention scale is 1/sqrt(head_dim)), as in the program.

Weights (``forward``'s ``w``): ``embed`` (V, d), ``final_norm`` (d,), and
under ``layers`` the per-layer stacks ``norm1``, ``norm2`` (L, d),
``router`` (L, d, E), ``wq`` ``wk`` ``wv`` ``wo`` (L, d_in, d_out) and
``gate`` ``up`` ``down`` (L, E, d_in, d_out).  A matrix may instead be a
compressed ``{"m_packed", "C"}``: tiles tn x td, row-block then
column-block, ``m_packed`` (..., r, c, tn, ceil(K/8)) uint8 little-endian
bits (1 = +1, 0 = -1) and ``C`` (..., r, c, K, td); it is decoded to the
dense M C here, one layer at a time.

``compare`` reads, over every served position of the sampled requests, the
relative error of the program's logits against the reference's,
||z - z*|| / ||z*||, and how far each served token's reference logit lies
below the reference's best.  The control is the same forward with both
operands of every matrix product rounded to float8 e4m3 (per-tensor absmax
scale) in the program's place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _q8, decode_M

# A token-layer is a near tie when the reference's router logits of its
# k-th and (k+1)-th expert lie closer than this: a gap that bfloat16
# rounding of the layer's input can close, flipping the expert chosen.
NEAR_TIE = 0.01


def _dense(w) -> jax.Array:
    """A matrix of ``w`` as float32, decoding a compressed one."""
    if not isinstance(w, dict):
        return w.astype(jnp.float32)
    mp, C = w["m_packed"], w["C"]
    *lead, r, c, tn, _ = mp.shape
    K, td = C.shape[-2:]
    M = decode_M(mp, K).reshape(*lead, r, c, tn, K)
    tiles = jnp.einsum("...rcnk,...rckd->...rncd", M, C.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    return tiles.reshape(*lead, r * tn, c * td)


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x (S, heads, hd): the published rotary embedding, half-split."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sizes(conf: dict) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return dict(d=d, H=H, KV=conf["num_key_value_heads"],
                hd=conf.get("head_dim") or d // H,
                E=conf["num_local_experts"], k=conf["num_experts_per_tok"],
                eps=conf["rms_norm_eps"], theta=float(conf["rope_theta"]))


@functools.partial(jax.jit, static_argnames=("sz", "fp8"))
def _forward(w, tokens, sz, fp8: bool):
    sz = dict(sz)
    d, H, KV, hd, E, k = (sz[n] for n in ("d", "H", "KV", "hd", "E", "k"))
    eps, theta = sz["eps"], sz["theta"]
    S = tokens.shape[0]
    emb = w["embed"].astype(jnp.float32)
    h = emb[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(h, lw):
        x = _rms(h, lw["norm1"], eps)
        q = _mm("sd,de->se", x, _dense(lw["wq"]), fp8).reshape(S, H, hd)
        kk = _mm("sd,de->se", x, _dense(lw["wk"]), fp8).reshape(S, KV, hd)
        v = _mm("sd,de->se", x, _dense(lw["wv"]), fp8).reshape(S, KV, hd)
        q, kk = _rope(q, theta), _rope(kk, theta)
        q = q.reshape(S, KV, H // KV, hd)
        s = _mm("igrh,jgh->grij", q, kk, fp8) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("grij,jgh->igrh", p, v, fp8).reshape(S, H * hd)
        h = h + _mm("se,ed->sd", o, _dense(lw["wo"]), fp8)

        x = _rms(h, lw["norm2"], eps)
        logits = _mm("sd,de->se", x, lw["router"].astype(jnp.float32), fp8)
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        gates = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(
            top / jnp.sum(top, -1, keepdims=True))
        ranked = jnp.sort(logits, axis=-1)
        tie = ranked[:, E - k] - ranked[:, E - k - 1] < NEAR_TIE
        g = _mm("sd,edf->sef", x, _dense(lw["gate"]), fp8)
        u = _mm("sd,edf->sef", x, _dense(lw["up"]), fp8)
        y = _mm("sef,efd->sed", jax.nn.silu(g) * u, _dense(lw["down"]), fp8)
        h = h + jnp.einsum("se,sed->sd", gates, y,
                           precision=jax.lax.Precision.HIGHEST)
        return h, tie

    h, ties = jax.lax.scan(layer, h, w["layers"])
    h = _rms(h, w["final_norm"], eps)
    return _mm("sd,vd->sv", h, emb, fp8), ties.T                # (S, V), (S, L)


def logits(conf: dict, w: dict, tokens, max_len: int, fp8: bool = False):
    """Reference logits (S, V) of ``tokens`` (S,) and each token-layer's
    near tie (S, L), run padded to ``max_len`` (one program for every
    length; causal, so the pad changes no earlier position)."""
    S = len(tokens)
    padded = np.zeros(max_len, np.int32)
    padded[:S] = tokens
    sz = tuple(sorted(_sizes(conf).items()))
    with jax.default_matmul_precision("highest"):
        z, ties = _forward(w, jnp.asarray(padded), sz, fp8)
    return z[:S], ties[:S]


def compare(conf: dict, w: dict, samples, max_len: int,
            control: bool = False) -> dict:
    """Readings over ``samples`` [(prompt (P,), served tokens (n,), the
    program's logits at each (n, V))]: ``logit_err_p50``/``_p90``/``_max``,
    the relative error's quantiles over served positions; ``token_gap``,
    the largest gap of a served token's reference logit below the
    reference's best; ``positions``; ``near_tie_share``, the share of the
    served positions' token-layers at a near routing tie.  With
    ``control``, the float8 forward's readings at the same positions, its
    own first choice as the served token, as ``control_<name>``."""
    errs, gaps, ties = [], [], []
    c_errs, c_gaps = [], []
    for prompt, served, z in samples:
        P, n = len(prompt), len(served)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        ref, tie = logits(conf, w, seq, max_len)
        ref = np.asarray(ref[P - 1:P - 1 + n])
        ties.append(np.asarray(tie[P - 1:P - 1 + n]))
        norm = np.linalg.norm(ref, axis=-1)
        errs.append(np.linalg.norm(z - ref, axis=-1) / norm)
        best = ref.max(-1)
        gaps.append(best - ref[np.arange(n), served])
        if control:
            c, _ = logits(conf, w, seq, max_len, fp8=True)
            c = np.asarray(c[P - 1:P - 1 + n])
            c_errs.append(np.linalg.norm(c - ref, axis=-1) / norm)
            c_gaps.append(best - ref[np.arange(n), c.argmax(-1)])
    out = _numbers(errs, gaps)
    out["positions"] = int(sum(len(e) for e in errs))
    out["near_tie_share"] = float(np.mean(np.concatenate(ties))) if ties else None
    if control:
        out |= {f"control_{k}": v for k, v in _numbers(c_errs, c_gaps).items()}
    return out


def _numbers(errs, gaps) -> dict:
    if not errs:
        return {"logit_err_p50": None, "logit_err_p90": None,
                "logit_err_max": None, "token_gap": None}
    e = np.concatenate(errs)
    return {"logit_err_p50": float(np.percentile(e, 50)),
            "logit_err_p90": float(np.percentile(e, 90)),
            "logit_err_max": float(e.max()),
            "token_gap": float(np.concatenate(gaps).max())}
