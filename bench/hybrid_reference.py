"""The plain float32 reference of a granite-4.0-h (``granitemoehybrid``)
decoder at one chip's expert share.  Nothing here imports the program.

The forward pass follows the published GraniteMoeHybrid description, read
from the configuration file's published keys:

- the token embedding times ``embedding_multiplier``;
- per layer ``h = x + r * mixer(rmsnorm(x))`` and then
  ``out = h + r * (moe(rmsnorm(h)) + shared(rmsnorm(h)))``, with ``r`` the
  ``residual_multiplier``; the mixer is the one ``layer_types`` names;
- a Mamba2 mixer (``mamba_*`` keys): one input projection to the gate z,
  the convolved stream (x, B, C) and the step dt; a causal depthwise
  convolution of width ``mamba_d_conv`` with bias and silu; then, position
  by position, the state recurrence
  ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T``, ``y_t = s_t C_t + D x_t``
  with ``dt_t = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``; the
  output gated by silu(z), RMS-normalised over the whole inner width (one
  group) and projected back.  This is the plain recurrence, not the
  chunked state-space-duality form the program computes;
- an attention mixer: causal grouped-query attention with no positional
  embedding (``position_embedding_type`` nope), scores scaled by
  ``attention_multiplier``;
- the MoE: the router scores all of its experts (the router matrix's
  width), takes the top ``num_experts_per_tok`` logits and a softmax over
  them; the experts held here, ``first`` to ``first`` +
  ``num_local_experts``, each a SwiGLU of width ``intermediate_size``,
  give their gate-weighted part for every token routed to them, and no
  token is dropped; the shared expert, a SwiGLU of width
  ``shared_intermediate_size``, is added for every token;
- a final RMSNorm (``rms_norm_eps``), then the tied embedding as the head,
  the logits divided by ``logits_scaling``.

Every matrix product runs in float32 at ``highest`` precision.  Departures:
none from the published layer equations; at one chip's share the routed
experts that are not held here are left out, as in the program.

Weights (``logits``' ``w``): ``embed`` (V, d), ``final_norm`` (d,), and
``layers``, one dict per layer: ``norm1``, ``norm2`` (d,); a Mamba2 layer's
``in_proj`` (d, 2 di + 2 ds + nh), ``conv_w`` (d_conv, di + 2 ds),
``conv_b``, ``A_log``, ``D``, ``dt_bias``, ``norm`` (di,) and ``out_proj``
(di, d); an attention layer's ``wq`` ``wk`` ``wv`` ``wo``; every layer's
``router`` (d, E), ``gate`` ``up`` (n, d, ff), ``down`` (n, ff, d) and
``shared_gate`` ``shared_up`` (d, fs), ``shared_down`` (fs, d).  A matrix
may instead be a compressed ``{"m_packed", "C"}``, decoded here by its own
bit decoding (``bench/serve_reference.py``), one layer at a time.

The control is the same forward with both operands of every matrix
product rounded to float8 e4m3 (per-tensor absmax scale).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.serve_reference import _dense, _mm, _numbers, _rms


def _sizes(conf: dict) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return dict(
        d=d, H=H, KV=conf["num_key_value_heads"],
        hd=conf.get("head_dim") or d // H, k=conf["num_experts_per_tok"],
        eps=conf["rms_norm_eps"], kinds=tuple(conf["layer_types"]),
        emb_mult=float(conf["embedding_multiplier"]),
        attn_mult=float(conf["attention_multiplier"]),
        res_mult=float(conf["residual_multiplier"]),
        logits_div=float(conf["logits_scaling"]),
        nh=conf["mamba_n_heads"], hp=conf["mamba_d_head"],
        ds=conf["mamba_d_state"], ng=conf["mamba_n_groups"],
    )


def _mamba(x, lw, sz, fp8):
    """The Mamba2 mixer over x (S, d), position by position."""
    S = x.shape[0]
    nh, hp, ds, ng = sz["nh"], sz["hp"], sz["ds"], sz["ng"]
    di = nh * hp
    zxbcdt = _mm("sd,de->se", x, _dense(lw["in_proj"]), fp8)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * ng * ds],
                  zxbcdt[:, 2 * di + 2 * ng * ds:])
    w = lw["conv_w"].astype(jnp.float32)                      # (K, ch)
    K = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = lw["conv_b"].astype(jnp.float32) + sum(
        w[i] * xp[i:i + S] for i in range(K))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(S, nh, hp)
    Bm = jnp.repeat(xbc[:, di:di + ng * ds].reshape(S, ng, ds), nh // ng, 1)
    Cm = jnp.repeat(xbc[:, di + ng * ds:].reshape(S, ng, ds), nh // ng, 1)
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))  # (S, nh)
    A = -jnp.exp(lw["A_log"].astype(jnp.float32))

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * A)[:, None, None] * s + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t,
                             precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, ds)), (xs, Bm, Cm, dt))
    y = y + lw["D"].astype(jnp.float32)[None, :, None] * xs
    y = y.reshape(S, di) * jax.nn.silu(z)
    y = _rms(y, lw["norm"].astype(jnp.float32), sz["eps"])
    return _mm("se,ed->sd", y, _dense(lw["out_proj"]), fp8)


def _attention(x, lw, sz, fp8):
    """Causal GQA over x (S, d), no positional embedding."""
    S = x.shape[0]
    H, KV, hd = sz["H"], sz["KV"], sz["hd"]
    q = _mm("sd,de->se", x, _dense(lw["wq"]), fp8).reshape(S, KV, H // KV, hd)
    k = _mm("sd,de->se", x, _dense(lw["wk"]), fp8).reshape(S, KV, hd)
    v = _mm("sd,de->se", x, _dense(lw["wv"]), fp8).reshape(S, KV, hd)
    s = _mm("igrh,jgh->grij", q, k, fp8) * sz["attn_mult"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("grij,jgh->igrh", p, v, fp8).reshape(S, H * hd)
    return _mm("se,ed->sd", o, _dense(lw["wo"]), fp8)


def _swiglu(x, g, u, dn, fp8):
    return _mm("sf,fd->sd", jax.nn.silu(_mm("sd,df->sf", x, g, fp8))
               * _mm("sd,df->sf", x, u, fp8), dn, fp8)


def _moe(x, lw, sz, first: int, fp8):
    """The held experts' gate-weighted part plus the shared expert."""
    S = x.shape[0]
    logits = _mm("sd,de->se", x, lw["router"].astype(jnp.float32), fp8)
    top, idx = jax.lax.top_k(logits, sz["k"])
    gates = jnp.zeros_like(logits).at[jnp.arange(S)[:, None], idx].set(
        jax.nn.softmax(top, axis=-1))
    gate, up, down = (_dense(lw[n]) for n in ("gate", "up", "down"))
    held = gates[:, first:first + gate.shape[0]]               # (S, n)
    y = _mm("sef,efd->sed",
            jax.nn.silu(_mm("sd,edf->sef", x, gate, fp8))
            * _mm("sd,edf->sef", x, up, fp8), down, fp8)
    out = jnp.einsum("se,sed->sd", held, y,
                     precision=jax.lax.Precision.HIGHEST)
    return out + _swiglu(x, _dense(lw["shared_gate"]), _dense(lw["shared_up"]),
                         _dense(lw["shared_down"]), fp8)


@functools.partial(jax.jit, static_argnames=("sz", "first", "fp8"))
def _forward(w, tokens, sz, first: int, fp8: bool):
    sz = dict(sz)
    eps, r = sz["eps"], sz["res_mult"]
    emb = w["embed"].astype(jnp.float32)
    h = emb[tokens] * sz["emb_mult"]
    for kind, lw in zip(sz["kinds"], w["layers"]):
        x = _rms(h, lw["norm1"].astype(jnp.float32), eps)
        mixer = _mamba if kind == "mamba" else _attention
        h = h + r * mixer(x, lw, sz, fp8)
        x = _rms(h, lw["norm2"].astype(jnp.float32), eps)
        h = h + r * _moe(x, lw, sz, first, fp8)
    h = _rms(h, w["final_norm"].astype(jnp.float32), eps)
    return _mm("sd,vd->sv", h, emb, fp8) / sz["logits_div"]


def logits(conf: dict, w: dict, tokens, max_len: int, first: int = 0,
           fp8: bool = False):
    """Reference logits (S, V) of ``tokens`` (S,), run padded to
    ``max_len`` (one program for every length; causal, so the pad changes
    no earlier position).  ``first`` is the first expert held here."""
    S = len(tokens)
    padded = np.zeros(max_len, np.int32)
    padded[:S] = tokens
    sz = tuple(sorted(_sizes(conf).items()))
    with jax.default_matmul_precision("highest"):
        z = _forward(w, jnp.asarray(padded), sz, first, fp8)
    return z[:S]


def compare(conf: dict, w: dict, samples, max_len: int, first: int = 0,
            control: bool = False) -> dict:
    """Readings over ``samples`` [(prompt (P,), served tokens (n,), the
    program's logits at each (n, V))], as ``serve_reference.compare``
    reads them: ``logit_err_p50``/``_p90``/``_max`` of ||z - z*|| / ||z*||
    over served positions, ``token_gap`` (the largest gap of a served
    token's reference logit below the reference's best) and
    ``positions``; with ``control``, the float8 forward's readings at the
    same positions, its own first choice as the served token, as
    ``control_<name>``."""
    errs, gaps, c_errs, c_gaps = [], [], [], []
    for prompt, served, z in samples:
        P, n = len(prompt), len(served)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        ref = np.asarray(logits(conf, w, seq, max_len, first)[P - 1:P - 1 + n])
        norm = np.linalg.norm(ref, axis=-1)
        errs.append(np.linalg.norm(z - ref, axis=-1) / norm)
        best = ref.max(-1)
        gaps.append(best - ref[np.arange(n), served])
        if control:
            c = np.asarray(logits(conf, w, seq, max_len, first, fp8=True)
                           [P - 1:P - 1 + n])
            c_errs.append(np.linalg.norm(c - ref, axis=-1) / norm)
            c_gaps.append(best - ref[np.arange(n), c.argmax(-1)])
    out = _numbers(errs, gaps)
    out["positions"] = int(sum(len(e) for e in errs))
    if control:
        out |= {f"control_{k}": v for k, v in _numbers(c_errs, c_gaps).items()}
    return out
