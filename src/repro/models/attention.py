"""GQA attention block with RoPE, optional qk-norm, sliding window, and a
KV cache for decode. Uses the Pallas flash kernel via kernels.ops."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.kernels import ops
from repro.utils import hints
from repro.models.layers import _he, apply_rope, init_rmsnorm, rmsnorm


def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, dtype=jnp.float32, qk_norm: bool = False):
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": _he(kq, (d_model, num_heads * head_dim), dtype, fan_in=d_model),
        "wk": _he(kk, (d_model, num_kv_heads * head_dim), dtype, fan_in=d_model),
        "wv": _he(kv, (d_model, num_kv_heads * head_dim), dtype, fan_in=d_model),
        "wo": _he(ko, (num_heads * head_dim, d_model), dtype,
                  fan_in=num_heads * head_dim),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(head_dim, dtype)
        p["k_norm"] = init_rmsnorm(head_dim, dtype)
    return p


def _split_heads(x, num_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, num_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def attention_block(
    params,
    x: jax.Array,                       # (B, S, d_model)
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    positions: jax.Array,               # (S,) absolute positions
    causal: bool = True,
    window: Optional[int] = None,
    rope_theta: float = 10_000.0,
    cache: Optional[dict] = None,       # {"k","v": (B,KVH,T,D), "len": ()}
) -> Tuple[jax.Array, Optional[dict]]:
    """Returns (output (B,S,d_model), updated cache).

    Prefill/training: cache=None, full-sequence flash attention.
    Decode: S==1; the new k/v are written at cache["len"] via dynamic slice
    update and attention runs against the whole cache buffer with position
    masking (cache length handled by the causal mask on absolute positions).
    """
    q = _split_heads(x @ params["wq"], num_heads, head_dim)
    k = _split_heads(x @ params["wk"], num_kv_heads, head_dim)
    v = _split_heads(x @ params["wv"], num_kv_heads, head_dim)

    if "q_norm" in params:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)

    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)

    if cache is None:
        out = ops.attention(q, k, v, causal=causal, window=window)
        new_cache = None
    elif hints.kv_time_sharded() and x.shape[1] == 1:
        # §Perf decode path: cache time dim sharded over the model axis;
        # write + local attention + distributed log-sum-exp merge
        pos = cache["len"]
        out, ck, cv = _decode_attention_kv_sharded(
            q, cache["k"], cache["v"], k, v, pos, window)
        new_cache = {"k": ck, "v": cv, "len": pos + x.shape[1]}
    else:
        # decode: write the new kv at the current cache position
        pos = cache["len"]
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=2)
        new_cache = {"k": ck, "v": cv, "len": pos + x.shape[1]}
        # q_offset = absolute position of the query token; keys beyond the
        # causal horizon are masked inside the kernel.
        out = _decode_attention(q, ck, cv, pos, window)
    return _merge_heads(out) @ params["wo"], new_cache


def _decode_attention(q, ck, cv, pos, window):
    """Single/few-token attention against the cache buffer.

    The flash kernel's q_offset is static; for decode we instead mask by
    absolute position computed from the traced ``pos`` using the reference
    path formulated with dynamic masks (XLA fuses this fine for S=1).
    """
    b, h, s, d = q.shape
    kvh, t = ck.shape[1], ck.shape[2]
    group = h // kvh
    kk = jnp.repeat(ck, group, axis=1)
    vv = jnp.repeat(cv, group, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    qpos = pos + jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def _decode_attention_kv_sharded(q, ck, cv, k_new, v_new, pos, window):
    """Decode attention with the KV cache's TIME dim sharded over 'model'.

    Motivation (§Perf): at decode_32k a 32k-token cache for a 4B model is
    ~38-216 GB per device when only batch-sharded — far over the 16GB HBM.
    Each model shard holds T/m positions; the new token's K/V are written
    by the owning shard; every shard computes attention over its slice and
    the partial (max, sum, weighted-V) triples merge with the standard
    flash/log-sum-exp combination via psum — O(B·H·D) collective, not
    O(B·H·T). Fully-manual shard_map (all axes manual) so no partial-auto
    machinery is involved.

    q: (B, H, 1, D) full heads; ck/cv: (B, KVH, T, D) time-sharded.
    Returns (out (B, H, 1, D), new_ck, new_cv).
    """
    from jax.sharding import PartitionSpec as P

    mesh = hints.get_mesh()
    baxes = hints.get_batch_axes()
    model_n = mesh.shape["model"]
    t_loc = ck.shape[2] // model_n

    def local(ql, ckl, cvl, knl, vnl):
        b, h, s, d = ql.shape
        kvh = ckl.shape[1]
        i = jax.lax.axis_index("model")
        t0 = i * t_loc
        # write the new K/V on the owning shard
        off = pos - t0
        owned = (off >= 0) & (off < t_loc)
        safe = jnp.clip(off, 0, t_loc - 1)
        ck2 = jax.lax.dynamic_update_slice_in_dim(ckl, knl, safe, axis=2)
        cv2 = jax.lax.dynamic_update_slice_in_dim(cvl, vnl, safe, axis=2)
        ckl = jnp.where(owned, ck2, ckl)
        cvl = jnp.where(owned, cv2, cvl)

        group = h // kvh
        kk = jnp.repeat(ckl, group, axis=1).astype(jnp.float32)
        vv = jnp.repeat(cvl, group, axis=1).astype(jnp.float32)
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
        logits = jnp.einsum("bhsd,bhtd->bhst", ql.astype(jnp.float32),
                            kk) * scale                     # (B,H,1,T_loc)
        kpos = t0 + jnp.arange(t_loc)[None, :]
        qpos = pos + jnp.arange(s)[:, None]
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None], logits, -jnp.inf)

        m_loc = jnp.max(logits, axis=-1)                    # (B,H,1)
        m_glb = jax.lax.pmax(m_loc, "model")
        # shards with no visible position contribute nothing
        corr = jnp.where(jnp.isfinite(m_loc),
                         jnp.exp(m_loc - m_glb), 0.0)
        e = jnp.where(jnp.isfinite(logits),
                      jnp.exp(logits - m_loc[..., None]), 0.0)
        s_loc = jnp.sum(e, axis=-1) * corr                  # (B,H,1)
        o_loc = jnp.einsum("bhst,bhtd->bhsd", e, vv) * corr[..., None]
        s_glb = jax.lax.psum(s_loc, "model")
        o_glb = jax.lax.psum(o_loc, "model")
        out = o_glb / jnp.maximum(s_glb[..., None], 1e-30)
        return out.astype(ql.dtype), ckl, cvl

    kv_spec = P(baxes, None, "model", None)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(baxes), kv_spec, kv_spec, P(baxes), P(baxes)),
        out_specs=(P(baxes), kv_spec, kv_spec),
        check_vma=False)
    return fn(q, ck, cv, k_new, v_new)


def init_attention_cache(batch: int, num_kv_heads: int, head_dim: int,
                         max_len: int, dtype=jnp.float32) -> dict:
    return {
        "k": jnp.zeros((batch, num_kv_heads, max_len, head_dim), dtype),
        "v": jnp.zeros((batch, num_kv_heads, max_len, head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }
