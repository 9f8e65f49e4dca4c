"""Mamba2 / SSD (state-space duality) sequence-mixing block.

Implements the chunked SSD algorithm (Dao & Gu, arXiv:2405.21060): the
sequence is split into chunks of length L; within a chunk the recurrence is
evaluated as a masked attention-like matmul (MXU-friendly), across chunks a
single per-head state (B, nh, hp, ds) is carried by a scan — O(S * L) work,
O(S) memory, exact.

Layer layout follows mamba2: fused in_proj -> (z, xBC, dt); causal depthwise
conv on xBC; SSD; gated RMSNorm; out_proj.  Decode carries (conv_state,
ssd_state) and is O(1) per token — this is what makes the ``long_500k`` cell
tractable (DESIGN.md §7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.params import Param, param

__all__ = ["init_ssm", "ssm_block", "init_ssm_cache"]


def _val(p):
    return p.value if isinstance(p, Param) else p


def init_ssm(key, cfg: ModelConfig, dtype) -> dict:
    d, di, ds, ng, nh = (
        cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_nheads,
    )
    conv_dim = di + 2 * ng * ds
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d_in_proj = 2 * di + 2 * ng * ds + nh
    # dt_bias: softplus^-1 of dt ~ loguniform[1e-3, 1e-1]
    dt = jnp.exp(
        jax.random.uniform(k3, (nh,), jnp.float32)
        * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3)
    )
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    a_init = jax.random.uniform(k4, (nh,), jnp.float32, 1.0, 16.0)
    return {
        "in_proj": layers.init_dense(k1, d, d_in_proj, ("embed", "ssm_in"), dtype),
        "conv_w": param(
            0.1 * jax.random.normal(k2, (cfg.ssm_dconv, conv_dim), jnp.float32).astype(dtype),
            (None, "ssm_in"),
        ),
        "conv_b": param(jnp.zeros((conv_dim,), dtype), ("ssm_in",)),
        "A_log": param(jnp.log(a_init), (None,)),
        "D": param(jnp.ones((nh,), jnp.float32), (None,)),
        "dt_bias": param(dt_bias, (None,)),
        "norm": {"scale": param(jnp.ones((di,), jnp.float32), ("ssm_in",))},
        "out_proj": layers.init_dense(k5, di, d, ("ssm_in", "embed"), dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array, state=None):
    """Depthwise causal conv, width dconv.  x (B, S, ch), w (dconv, ch).
    Returns (y, new_state) with state = last (dconv-1) inputs."""
    B, S, ch = x.shape
    dconv = w.shape[0]
    if state is None:
        state = jnp.zeros((B, dconv - 1, ch), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = b
    for i in range(dconv):
        y = y + w[i] * jax.lax.dynamic_slice_in_dim(xp, i, S, axis=1)
    new_state = xp[:, S:, :] if S >= dconv - 1 else xp[:, -(dconv - 1):, :]
    return jax.nn.silu(y), new_state


def _ssd_chunk(u, dA_cum, Bm, Cm, S_prev, rep):
    """One chunk of the SSD recurrence.

    u (B, L, nh, hp); dA_cum (B, L, nh) inclusive cumsum of log-decay;
    Bm/Cm (B, L, g, ds); S_prev (B, nh, hp, ds).  Returns (y, S_new).
    """
    decay = jnp.exp(dA_cum[:, :, None, :] - dA_cum[:, None, :, :])      # (B,L,L,nh)
    L = u.shape[1]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.where(causal[None, :, :, None], decay, 0.0)
    CB = jnp.einsum("blgn,bsgn->blsg", Cm, Bm)                          # (B,L,L,g)
    CB = jnp.repeat(CB, rep, axis=-1)                                   # g -> nh
    scores = (CB * decay).astype(u.dtype)
    y_intra = jnp.einsum("blsh,bshp->blhp", scores, u)

    last = dA_cum[:, -1:, :]                                            # (B,1,nh)
    Ch = jnp.repeat(Cm, rep, axis=2)                                    # (B,L,nh,ds)
    y_inter = jnp.einsum("blhn,bhpn->blhp", Ch.astype(jnp.float32), S_prev.astype(jnp.float32))
    y_inter = y_inter * jnp.exp(dA_cum)[..., None]

    w_state = jnp.exp(last - dA_cum)                                    # (B,L,nh)
    Bh = jnp.repeat(Bm, rep, axis=2)                                    # (B,L,nh,ds)
    S_chunk = jnp.einsum(
        "blh,blhn,blhp->bhpn",
        w_state.astype(jnp.float32),
        Bh.astype(jnp.float32),
        u.astype(jnp.float32),
    )
    S_new = S_prev * jnp.exp(last[:, 0, :])[:, :, None, None] + S_chunk
    return y_intra + y_inter.astype(u.dtype), S_new


def _ssd(u, dA, Bm, Cm, chunk: int, S0, unroll: bool):
    """Full-sequence SSD. u (B,S,nh,hp), dA (B,S,nh) log-decay per step,
    Bm/Cm (B,S,g,ds). Returns (y, S_final).

    A length that no chunk count divides evenly is padded at the end to a
    multiple of ``chunk``: a pad step has no input (u = 0) and no decay
    (dA = 0), so it leaves the state as it was, and its outputs are
    dropped."""
    B, S0_len, nh, hp = u.shape
    nc = max(S0_len // chunk, 1)
    if S0_len % nc:
        nc = -(-S0_len // chunk)
        pad = nc * chunk - S0_len
        padded = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        u, dA, Bm, Cm = padded(u), padded(dA), padded(Bm), padded(Cm)
    B, S, nh, hp = u.shape
    g = Bm.shape[2]
    rep = nh // g
    L = S // nc
    cs = lambda a: a.reshape(B, nc, L, *a.shape[2:])
    uc, dAc, Bc, Cc = cs(u), cs(dA), cs(Bm), cs(Cm)
    dA_cum = jnp.cumsum(dAc, axis=2)                                    # (B,nc,L,nh)

    if unroll:
        ys = []
        Sst = S0
        for c in range(nc):
            y, Sst = _ssd_chunk(uc[:, c], dA_cum[:, c], Bc[:, c], Cc[:, c], Sst, rep)
            ys.append(y)
        return jnp.concatenate(ys, axis=1).reshape(B, S, nh, hp)[:, :S0_len], Sst

    def step(Sst, xs):
        ucc, dcc, bcc, ccc = xs
        y, S_new = _ssd_chunk(ucc, dcc, bcc, ccc, Sst, rep)
        return S_new, y

    xs = (
        uc.transpose(1, 0, 2, 3, 4),
        dA_cum.transpose(1, 0, 2, 3),
        Bc.transpose(1, 0, 2, 3, 4),
        Cc.transpose(1, 0, 2, 3, 4),
    )
    S_final, ys = jax.lax.scan(step, S0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, nh, hp)
    return y[:, :S0_len], S_final


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    di, ds, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads
    hp = cfg.ssm_headdim
    conv_dim = di + 2 * cfg.ssm_ngroups * ds
    return {
        "conv": jnp.zeros((batch, cfg.ssm_dconv - 1, conv_dim), dtype),
        "state": jnp.zeros((batch, nh, hp, ds), jnp.float32),
    }


def ssm_block(
    h: jax.Array,
    p: dict,
    cfg: ModelConfig,
    *,
    cache: dict | None = None,
    unroll: bool = False,
):
    """Returns (out (B,S,d), new_cache)."""
    B, S, d = h.shape
    di, ds, ng, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_nheads
    hp = cfg.ssm_headdim

    zxbcdt = layers.apply_dense(h, p["in_proj"])
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di : 2 * di + 2 * ng * ds]
    dt_raw = zxbcdt[..., 2 * di + 2 * ng * ds :]                        # (B,S,nh)

    conv_state = cache["conv"] if cache is not None else None
    xBC, new_conv = _causal_conv(xBC, _val(p["conv_w"]), _val(p["conv_b"]), conv_state)

    x = xBC[..., :di].reshape(B, S, nh, hp)
    Bm = xBC[..., di : di + ng * ds].reshape(B, S, ng, ds)
    Cm = xBC[..., di + ng * ds :].reshape(B, S, ng, ds)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + _val(p["dt_bias"]))
    A = -jnp.exp(_val(p["A_log"]))                                      # (nh,)
    dA = dt * A                                                         # (B,S,nh) log-decay
    u = x * dt.astype(x.dtype)[..., None]

    S0 = (
        cache["state"]
        if cache is not None
        else jnp.zeros((B, nh, hp, ds), jnp.float32)
    )
    if S == 1 and cache is not None:
        # ---- O(1) decode step ----
        a = jnp.exp(dA[:, 0])                                           # (B,nh)
        rep = nh // ng
        Bh = jnp.repeat(Bm[:, 0], rep, axis=1)                          # (B,nh,ds)
        Ch = jnp.repeat(Cm[:, 0], rep, axis=1)
        S_new = S0 * a[..., None, None] + jnp.einsum(
            "bhn,bhp->bhpn", Bh.astype(jnp.float32), u[:, 0].astype(jnp.float32)
        )
        y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(jnp.float32), S_new)
        y = y[:, None].astype(h.dtype)
        S_final = S_new
    else:
        chunk = min(cfg.ssm_chunk, S)
        # checkpoint: the SSD chunk scan otherwise saves per-chunk decay /
        # score tensors fp32 for backward (~270 MB x layers on zamba2 train;
        # EXPERIMENTS.md §Perf) — recompute them instead.
        ssd_fn = jax.checkpoint(
            lambda u_, dA_, B_, C_, S0_: _ssd(u_, dA_, B_, C_, chunk, S0_, unroll),
            policy=jax.checkpoint_policies.nothing_saveable,
        )
        y, S_final = ssd_fn(u, dA, Bm, Cm, S0)

    y = y + _val(p["D"]).astype(y.dtype)[None, None, :, None] * x
    y = y.reshape(B, S, di)
    y = layers.rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = layers.apply_dense(y, p["out_proj"])

    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "state": S_final}
    return out, new_cache
