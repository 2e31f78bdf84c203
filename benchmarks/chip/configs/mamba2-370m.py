"""mamba2-370m: 48 Mamba-2 (SSD) blocks, d_model 1024, 32 heads of 64,
d_state 128, tied embeddings over a 50,277-token vocabulary, bfloat16.

- ``program_config`` / ``program_params``: the program's model config
  and its weights, built on the device from the seed in one jitted call.
- ``reference_logits``: a plain float32 Mamba-2 forward written out in
  ``jax.numpy`` at ``highest`` precision, one layer at a time, with the
  state recurrence as a sequential scan over positions. It follows
  mamba_ssm's ``Mamba2`` block as the configuration states it: the
  depthwise convolution's bias (``conv_bias``), the gated norm
  ``rmsnorm(y * silu(z))`` (``norm_before_gate`` false; true gives
  ``rmsnorm(y) * silu(z)``) and ``norm_epsilon`` in every norm. Being
  float32 throughout, it keeps the residual in float32. ``mode="fp8"``
  is the control: every matrix product with both operands rounded to
  float8 e4m3 (scaled per row of activations and per output column of
  weights), the precision one step below the configuration's bfloat16.
- ``departures``: where the program cannot run the configuration as it
  is stated; ``program_config`` refuses such a configuration.
- ``flops``: forward operations of the window's tokens, from the shapes.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
CONTROL_MODE = "fp8"


def dims(cfg: dict) -> dict:
    d, e, hd = cfg["d_model"], cfg["expand"], cfg["headdim"]
    di = d * e
    v = cfg["vocab_size"]
    pad = cfg["pad_vocab_size_multiple"]
    return dict(d=d, di=di, n=cfg["d_state"], h=di // hd, p=hd,
                k=cfg["d_conv"], L=cfg["n_layer"], vocab=v,
                padded_vocab=-(-v // pad) * pad, eps=cfg["norm_epsilon"],
                conv_bias=cfg["conv_bias"],
                norm_before_gate=cfg["norm_before_gate"])


# what the program's SSD model does where mamba_ssm gives a choice
# (repro.models.layers.ssd_block_apply and rmsnorm)
PROGRAM_BLOCK = {"conv_bias": False, "norm_before_gate": True,
                 "norm_epsilon": 1e-6}


def departures(cfg: dict) -> list[str]:
    """The settings of ``cfg`` that the program cannot run as stated."""
    out = [f"{k}={cfg[k]!r} (the program: {v!r})"
           for k, v in PROGRAM_BLOCK.items() if cfg[k] != v]
    if cfg["residual_in_fp32"] and cfg["dtype"] != "float32":
        out.append(f"residual_in_fp32=True (the program keeps the residual "
                   f"in {cfg['dtype']})")
    if not (cfg["ngroups"] == 1 and cfg["d_intermediate"] == 0
            and cfg["rms_norm"] and not cfg["attn_layer_idx"]
            and cfg["tie_embeddings"]):
        out.append("the program runs one group of B/C, no MLP, no "
                   "attention layers, RMSNorm and a tied head")
    return out


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration; a
    configuration the program departs from is refused."""
    from repro.models import ModelConfig

    if departures(cfg):
        raise ValueError("the program cannot run this configuration as "
                         "stated: " + "; ".join(departures(cfg)))
    m = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="ssm", mixer="ssd", ffn="none",
        n_layers=m["L"], d_model=m["d"], n_heads=m["h"], n_kv=m["h"],
        d_ff=0, vocab=m["vocab"], d_state=m["n"],
        ssd_expand=cfg["expand"], ssd_headdim=m["p"],
        ssd_chunk=cfg["chunk_size"], conv_k=m["k"],
        vocab_pad=cfg["pad_vocab_size_multiple"],
        tie_embeddings=cfg["tie_embeddings"], dtype=cfg["dtype"],
        ssd_state_dtype=cfg["ssm_state_dtype"])


def _key(cfg: dict) -> str:
    return json.dumps(cfg, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _draw_fn(cfg_key: str):
    cfg = json.loads(cfg_key)
    m = dims(cfg)
    d, di, n, h, k, L = m["d"], m["di"], m["n"], m["h"], m["k"], m["L"]
    dt = jnp.dtype(cfg["dtype"])

    def uni(key, shape, bound):
        return jax.random.uniform(key, shape, F32, -bound, bound)

    def layer(key):
        ks = jax.random.split(key, 6)
        dt0 = jnp.exp(jax.random.uniform(
            ks[4], (h,), F32, math.log(1e-3), math.log(1e-1)))
        dt0 = jnp.maximum(dt0, 1e-4)
        return {
            "norm1": jnp.ones((d,), dt),
            "mixer": {
                "w_in": uni(ks[0], (d, 2 * di + 2 * n + h),
                            1 / math.sqrt(d)).astype(dt),
                "conv_w": uni(ks[1], (k, di + 2 * n),
                              1 / math.sqrt(k)).astype(dt),
                "conv_b": uni(ks[5], (di + 2 * n,),
                              1 / math.sqrt(k)).astype(dt),
                "a_log": jnp.log(jax.random.uniform(
                    ks[3], (h,), F32, 1.0, 16.0)),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "d_skip": jnp.ones((h,), F32),
                "out_norm": jnp.ones((di,), dt),
                "w_out": (uni(ks[2], (di, d), 1 / math.sqrt(di))
                          / math.sqrt(L)).astype(dt),
            },
        }

    def draw(key):
        k_emb, k_layers = jax.random.split(key)
        return {
            "embed": (0.02 * jax.random.normal(
                k_emb, (m["padded_vocab"], d), F32)).astype(dt),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, L)),
            "final_norm": jnp.ones((d,), dt),
        }

    return jax.jit(draw)


def program_params(cfg: dict, key):
    """The served weights, in the program's layout and dtype (tied
    head: no separate head is drawn; the program's convolution has no
    bias)."""
    p = _draw_fn(_key(cfg))(key)
    p["layers"]["mixer"].pop("conv_b")
    return p


def reference_params(cfg: dict, key):
    """The model's weights in float32, the convolution's bias included.
    The draw depends on the shapes alone, not on the block's settings."""
    return jax.tree.map(lambda a: a.astype(F32), _draw_fn(_key(cfg))(key))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _fp8(a, axis: int):
    """``a`` rounded to float8 e4m3, scaled along ``axis``'s maximum."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, mode: str):
    """x @ w over the last axis of x, w: (in, out)."""
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _layer(lp, x, m: tuple, mode: str):
    """One Mamba-2 block and its residual add. x: (B, T, d) float32."""
    m = dict(m)
    di, n, h, p, k, eps = (m[s] for s in ("di", "n", "h", "p", "k", "eps"))
    conv_bias, norm_before_gate = m["conv_bias"], m["norm_before_gate"]
    mx = lp["mixer"]
    b, t, _ = x.shape
    u = _rms(x, lp["norm1"], eps)
    zxbcdt = _mm(u, mx["w_in"], mode)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * n]
    dt = zxbcdt[..., 2 * di + 2 * n:]
    # causal depthwise convolution of width k over positions
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * mx["conv_w"][i] for i in range(k))
    if conv_bias:
        conv = conv + mx["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(b, t, h, p)
    bmat, cmat = xbc[..., di:di + n], xbc[..., di + n:]
    a = -jnp.exp(mx["a_log"])                               # (H,)
    dt = jax.nn.softplus(dt + mx["dt_bias"])                # (B, T, H)

    def step(s, inp):
        # s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T;  y_t = s_t C_t
        x_t, b_t, c_t, dt_t = inp
        s = (s * jnp.exp(dt_t * a)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HIGHEST)

    s0 = jnp.zeros((b, h, p, n), F32)
    tm = lambda a_: jnp.moveaxis(a_, 1, 0)                  # noqa: E731
    _, ys = jax.lax.scan(step, s0, (tm(xs), tm(bmat), tm(cmat), tm(dt)))
    y = jnp.moveaxis(ys, 0, 1) + xs * mx["d_skip"][:, None]
    y = y.reshape(b, t, di)
    if norm_before_gate:
        y = _rms(y, mx["out_norm"], eps) * jax.nn.silu(z)
    else:
        y = _rms(y * jax.nn.silu(z), mx["out_norm"], eps)
    return x + _mm(y, mx["w_out"], mode)


@functools.partial(jax.jit, static_argnames=("m", "mode", "start"))
def _head(params, x, m: tuple, mode: str, start: int):
    m = dict(m)
    hid = _rms(x[:, start:], params["final_norm"], m["eps"])
    emb = params["embed"]
    if mode == "fp8":
        emb = _fp8(emb, 1)
    logits = jnp.einsum("btd,vd->btv", hid, emb, precision=HIGHEST)
    return logits[..., :m["vocab"]]


def reference_logits(cfg: dict, params, tokens, start: int,
                     mode: str = "f32"):
    """Logits (B, T - start, vocab) at positions ``start..T-1`` of
    ``tokens`` (B, T), from float32 ``params``."""
    m = tuple(sorted(dims(cfg).items()))
    emb = params["embed"]
    if mode == "fp8":
        emb = _fp8(emb, 1)
    x = emb[jnp.asarray(tokens)]
    for i in range(dims(cfg)["L"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer(lp, x, m, mode)
    return _head(params, x, m, mode, start)


def flops(cfg: dict, layer_tokens: int, logit_tokens: int) -> float:
    """Forward operations for ``layer_tokens`` tokens through every
    block and ``logit_tokens`` rows of logits: twice the weights of each
    product, the convolution, and the recurrence's state update and
    read-out (4 H P N a token)."""
    m = dims(cfg)
    d, di, n, h, p, k = m["d"], m["di"], m["n"], m["h"], m["p"], m["k"]
    per_layer = (2 * d * (2 * di + 2 * n + h) + 2 * di * d
                 + 2 * k * (di + 2 * n) + 4 * h * p * n)
    return (layer_tokens * m["L"] * per_layer
            + logit_tokens * 2 * d * m["vocab"])
