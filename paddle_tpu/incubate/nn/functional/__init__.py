"""Fused functional APIs (reference: python/paddle/incubate/nn/functional —
16 fused entry points).  Each dispatches to the Pallas kernel inventory."""

from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from ....core.dispatch import run_op
from ....core.tensor import Tensor
from ....ops import pallas as _pk

__all__ = [
    "fused_rms_norm", "fused_layer_norm",
    "fused_bias_dropout_residual_layer_norm", "fused_rotary_position_embedding",
    "fused_bias_act", "fused_dropout_add", "swiglu", "fused_linear",
    "fused_linear_activation", "fused_multi_head_attention",
    "masked_multihead_attention", "fused_multi_transformer",
    "fused_conv_bn_act", "fused_adam", "fused_matmul_bias",
    "fused_feedforward", "blha_get_max_len", "block_multihead_attention",
    "variable_length_memory_efficient_attention", "fused_moe",
    "fused_ec_moe",
]


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None):
    def impl(xv, w, nb, b, res):
        if b is not None:
            xv = xv + b
        if res is not None:
            xv = xv + res
        out = _pk.rms_norm(xv, w, epsilon)
        if nb is not None:
            out = out + nb
        return (out, xv) if res is not None else out
    return run_op("fused_rms_norm", impl,
                  (x, norm_weight, norm_bias, bias, residual), {})


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None):
    def impl(xv, w, b, bias_v, res):
        if bias_v is not None:
            xv = xv + bias_v
        if res is not None:
            xv = xv + res
        out = _pk.layer_norm(xv, w, b, epsilon)
        return (out, xv) if res is not None else out
    return run_op("fused_layer_norm", impl,
                  (x, norm_weight, norm_bias, bias, residual), {})


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=False):
    def impl(xv, res, b, w, lb):
        if b is None:
            b = jnp.zeros(xv.shape[-1], xv.dtype)
        if w is None:
            w = jnp.ones(xv.shape[-1], jnp.float32)
        if lb is None:
            lb = jnp.zeros(xv.shape[-1], jnp.float32)
        out, _ = _pk.fused_bias_dropout_residual_layer_norm(
            xv, res, b, w, lb, dropout_rate, ln_epsilon, training)
        return out
    return run_op("fused_bias_dropout_residual_ln", impl,
                  (x, residual, bias, ln_scale, ln_bias), {})


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True):
    def impl(qv, kv, vv, sv, cv, pid):
        return _pk.fused_rope(qv, kv, vv, sv, cv, pid,
                              use_neox_rotary_style)
    return run_op("fused_rope", impl, (q, k, v, sin, cos, position_ids), {})


def fused_bias_act(x, bias, act_method="gelu"):
    return run_op("fused_bias_act",
                  lambda xv, b: _pk.fused_bias_act(xv, b, act_method),
                  (x, bias), {})


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    from ....core.rng import next_rng_key
    import jax as _jax
    seed = _jax.random.randint(next_rng_key(), (), 0, 2 ** 31 - 1)         if training and p > 0.0 else None
    return run_op("fused_dropout_add",
                  lambda xv, yv, sd: _pk.fused_dropout_add(
                      xv, yv, p, training, seed=sd),
                  (x, y, seed), {})


def swiglu(x, y=None):
    def impl(xv, yv):
        if yv is None:
            h = xv.shape[-1] // 2
            xv, yv = xv[..., :h], xv[..., h:]
        return _pk.swiglu(xv, yv)
    return run_op("fused_swiglu", impl, (x, y), {})


def fused_linear(x, weight, bias=None, transpose_weight=False):
    def impl(xv, w, b):
        if transpose_weight:
            w = jnp.swapaxes(w, -2, -1)
        out = jnp.matmul(xv, w)
        if b is not None:
            out = out + b
        return out
    return run_op("fused_linear", impl, (x, weight, bias), {})


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu"):
    def impl(xv, w, b):
        if trans_x:
            xv = jnp.swapaxes(xv, -2, -1)
        if trans_y:
            w = jnp.swapaxes(w, -2, -1)
        return _pk.fused_bias_act(jnp.matmul(xv, w), b, activation)
    return run_op("fused_linear_activation", impl, (x, y, bias), {})


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.0,
                               attn_dropout_rate=0.0, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, num_heads=-1,
                               transpose_qkv_wb=False, name=None):
    """Monolithic fused MHA block (reference
    incubate/nn/functional/fused_transformer.py / fused_attention op):
    [pre-LN →] fused QKV proj → attention → out proj → dropout →
    [+residual →] [post-LN].  qkv_weight: [3, H, D, E] (paddle layout), or
    [E, 3*E] with ``transpose_qkv_wb``.  Attention dispatches to the flash
    kernel via F.scaled_dot_product_attention."""
    from ....core.rng import next_rng_key
    from ....nn import functional as F

    if cache_kv is not None:
        raise NotImplementedError(
            "fused_multi_head_attention: decode with cache_kv goes through "
            "masked_multihead_attention / models.generation")
    if ring_id not in (-1, None):
        raise NotImplementedError(
            "fused_multi_head_attention: tensor-parallel ring_id is not "
            "wired; use the manual-SPMD block path (parallel/manual.py)")
    if mode != "upscale_in_train":
        raise NotImplementedError(
            f"fused_multi_head_attention: dropout mode {mode!r}")

    # rng keys are operands, not trace-time constants: run_op caches the
    # traced executable per shape, so a key drawn inside impl would bake
    # one dropout mask forever (same convention as fused_dropout_add)
    drop_key = (next_rng_key() if dropout_rate > 0.0 and training else None)

    def impl(xv, qkvw, lw, plns, plnb, lns, lnb, qkvb, lb, mask, dkey):
        B, S, E = xv.shape
        if transpose_qkv_wb:
            nh = num_heads
            qkvw_ = qkvw.reshape(E, 3, nh, E // nh)
            qkvw_ = jnp.transpose(qkvw_, (1, 2, 3, 0))
            if qkvb is not None:
                qkvb = qkvb.reshape(3, nh, E // nh)
        else:
            qkvw_ = qkvw
            nh = qkvw_.shape[1]
        hd = qkvw_.shape[2]
        y = xv
        if pre_layer_norm:
            mu = jnp.mean(y, -1, keepdims=True)
            var = jnp.var(y, -1, keepdims=True)
            y = (y - mu) * jax.lax.rsqrt(var + pre_ln_epsilon)
            if plns is not None:
                y = y * plns
            if plnb is not None:
                y = y + plnb
        qkv = jnp.einsum("bse,thde->bsthd", y, qkvw_)
        if qkvb is not None:
            qkv = qkv + qkvb[None, None]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,S,H,D]
        attn = F.scaled_dot_product_attention(
            q, k, v, attn_mask=mask,
            dropout_p=attn_dropout_rate if training else 0.0,
            is_causal=False, training=training)
        attn = jnp.asarray(attn._value if hasattr(attn, "_value") else attn)
        out = attn.reshape(B, S, nh * hd) @ lw
        if lb is not None:
            out = out + lb
        if dkey is not None:
            keep = jax.random.bernoulli(dkey, 1.0 - dropout_rate, out.shape)
            out = jnp.where(keep, out / (1.0 - dropout_rate), 0.0)
        if add_residual:
            out = xv + out
        if not pre_layer_norm:
            mu = jnp.mean(out, -1, keepdims=True)
            var = jnp.var(out, -1, keepdims=True)
            out = (out - mu) * jax.lax.rsqrt(var + ln_epsilon)
            if lns is not None:
                out = out * lns
            if lnb is not None:
                out = out + lnb
        return out

    return run_op("fused_multi_head_attention", impl,
                  (x, qkv_weight, linear_weight, pre_ln_scale, pre_ln_bias,
                   ln_scale, ln_bias, qkv_bias, linear_bias, attn_mask,
                   drop_key), {})


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               sequence_lengths=None, rotary_tensor=None,
                               beam_cache_offset=None, qkv_out_scale=None,
                               out_shift=None, out_smooth=None, seq_len=1,
                               rotary_emb_dims=0, use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """Decode-step MMHA (reference
    incubate/nn/functional/masked_multihead_attention.py →
    masked_multihead_attention_kernel.cu): one token's fused QKV attends to
    a preallocated cache.  x: [B, 3*H*D]; cache_kv: [2, B, H, T_max, D].
    Returns (out [B, H*D], updated cache_kv).  Dispatches to the Pallas
    decode kernel on TPU (ops/pallas/decode_attention.py)."""
    from ....ops.pallas.decode_attention import decode_attention

    if rotary_tensor is not None and not rotary_emb_dims:
        rotary_emb_dims = 1
    if rotary_emb_dims and rotary_tensor is None:
        raise ValueError("masked_multihead_attention: rotary_emb_dims set "
                         "but rotary_tensor is None")
    if rotary_emb_dims not in (0, 1, 2):
        raise ValueError(f"rotary_emb_dims must be 0/1/2, got "
                         f"{rotary_emb_dims}")
    if beam_cache_offset is not None and cache_kv is None:
        raise ValueError("masked_multihead_attention: beam_cache_offset "
                         "requires cache_kv")
    if (out_shift is None) != (out_smooth is None):
        raise ValueError("masked_multihead_attention: out_shift and "
                         "out_smooth must be provided together (the "
                         "reference store applies (out+shift)*smooth)")
    quant_out = out_scale is not None and out_scale > 0
    if beam_cache_offset is not None:
        _bo = getattr(beam_cache_offset, "_value", beam_cache_offset)
        _ck = getattr(cache_kv, "_value", cache_kv)
        if _bo.ndim != 3 or _bo.shape[0] * _bo.shape[1] != _ck.shape[1]:
            raise ValueError(
                "beam_cache_offset must be [batch, beam_size, "
                "max_seq_len + max_dec_len] with batch*beam_size == "
                f"cache rows; got {tuple(_bo.shape)} vs cache "
                f"{tuple(_ck.shape)}")
        if _bo.shape[-1] != _ck.shape[3]:
            # the kernel reads offsets at every past position, so the
            # offset table must cover exactly the cache capacity — a
            # short table would silently zero-pad (reading beam 0's
            # cache) and a long one silently truncate
            raise ValueError(
                "beam_cache_offset last dim must equal the cache "
                f"capacity (cache_kv.shape[3] == {_ck.shape[3]}); got "
                f"{_bo.shape[-1]}")
    # capacity check must run on the CONCRETE lengths out here — inside
    # impl they are tracers under the default eager-op jit cache, and a
    # full cache would silently drop the scatter (JAX OOB semantics)
    if sequence_lengths is not None and cache_kv is not None:
        import numpy as _np
        _sl = sequence_lengths
        _sl = _sl._value if isinstance(_sl, Tensor) else _sl
        cap = (cache_kv._value if isinstance(cache_kv, Tensor)
               else cache_kv).shape[3]
        if not isinstance(_sl, jax.core.Tracer):
            mx = int(_np.max(_np.asarray(_sl)))
            if mx >= cap:
                raise ValueError(
                    f"masked_multihead_attention: cache full (length {mx} "
                    f">= capacity {cap})")

    def _apply_mmha_rope(q, k, rot, lens):
        """Reference mmha kernel rotary (masked_multihead_attention_
        kernel.cu:247-): ``rot`` packs a cos plane then a sin plane
        ([2, B, rotary_seq_len, 1, dim_head], the kernel comment's
        layout).  rotary_seq_len == 1 means the caller pre-gathered the
        row at the current position; a full table (rotary_seq_len > 1)
        is gathered here at each row's current length.  non-neox:
        interleaved per-element transform (q2i, q2i+1 rotated with
        cos/sin at those same elements); neox: half-rotation within each
        of ``rotary_emb_dims`` sections."""
        B, H, D = q.shape
        rot = rot.astype(jnp.float32)
        if rot.shape[0] != 2 or rot.size % (2 * B * D):
            raise ValueError("rotary_tensor must pack [2 (cos,sin), B, "
                             f"rotary_seq_len, 1, {D}]; got shape "
                             f"{rot.shape}")
        table = rot.reshape(2, B, -1, D)            # [2, B, S_rot, D]
        if table.shape[2] == 1:
            table = table[:, :, 0]                  # pre-gathered row
        else:                                       # gather at position
            pos = jnp.clip(lens, 0, table.shape[2] - 1)
            table = table[:, jnp.arange(B), pos]    # [2, B, D]
        cos = table[0][:, None]                     # [B, 1, D]
        sin = table[1][:, None]

        def tr(t):
            tf = t.astype(jnp.float32)
            if not use_neox_rotary_style:
                x = tf[..., 0::2]
                y = tf[..., 1::2]
                x2 = x * cos[..., 0::2] - y * sin[..., 0::2]
                y2 = y * cos[..., 1::2] + x * sin[..., 1::2]
                out = jnp.stack([x2, y2], axis=-1).reshape(B, H, D)
            else:
                last = D // rotary_emb_dims
                half = last // 2
                sec = tf.reshape(B, H, rotary_emb_dims, last)
                cs = cos.reshape(B, 1, rotary_emb_dims, last)
                sn = sin.reshape(B, 1, rotary_emb_dims, last)
                x = sec[..., :half]
                y = sec[..., half:]
                x2 = x * cs[..., :half] - y * sn[..., :half]
                y2 = y * cs[..., half:] + x * sn[..., half:]
                out = jnp.concatenate([x2, y2], -1).reshape(B, H, D)
            return out.astype(t.dtype)

        return tr(q), tr(k)

    def impl(xv, cache, b, seqlens, rot, smask, beam_off, qkv_scale,
             oshift, osmooth):
        B = xv.shape[0]
        H, T, D = cache.shape[2], cache.shape[3], cache.shape[4]
        if qkv_scale is not None:
            # int32 fused-QKV-matmul output dequantized per channel
            # (reference MMHALoad<T, int32_t>: x * dequant_scales[c],
            # scale layout [3, H, D] == the flat 3HD channel axis)
            xv = xv.astype(jnp.float32) * \
                qkv_scale.astype(jnp.float32).reshape(-1)[None, :]
        if b is not None:
            xv = xv + b
        q, k, v = (a[:, 0] for a in jnp.split(
            xv.reshape(B, 3, H, D), 3, axis=1))
        if seqlens is None:
            raise ValueError("masked_multihead_attention needs "
                             "sequence_lengths (cache fill per row)")
        lens = seqlens.reshape(B).astype(jnp.int32)
        if rot is not None:
            q, k = _apply_mmha_rope(q, k, rot, lens)
        # scatter this step's k/v at each row's current length (capacity
        # validated on the concrete lengths in the outer function)
        tpos = lens  # [B]
        bidx = jnp.arange(B)
        kc = cache[0].at[bidx, :, tpos].set(k.astype(cache.dtype))
        vc = cache[1].at[bidx, :, tpos].set(v.astype(cache.dtype))
        if smask is not None or beam_off is not None:
            # dense masked path, one fused XLA step (reference mmha_naive:
            # product + src_mask before softmax).  Beam search also lands
            # here: per past position t, row (bbi, beami) reads the cache
            # row of beam beam_off[bbi, beami, t] within its real batch
            # (kernel.cu:417-441 k_cache_batch + beam_offset indexing),
            # so KV is no longer a per-row [H, T, D] block.
            if beam_off is not None:
                bw = beam_off.shape[1]
                offT = beam_off.reshape(B, -1)[:, :T].astype(jnp.int32)
                if offT.shape[1] < T:      # offsets shorter than capacity:
                    offT = jnp.pad(offT, ((0, 0), (0, T - offT.shape[1])))
                src = (jnp.arange(B)[:, None] // bw) * bw + offT   # [B, T]
                # beam offsets cover PAST positions only (kernel.cu:423:
                # ti < tlength); the current step's K/V — scattered above
                # at each row's own length — always reads the own row
                src = src.at[jnp.arange(B), lens].set(jnp.arange(B))
                # k_eff[b, t] = kc[src[b, t], :, t]
                k_eff = kc[src, :, jnp.arange(T)[None, :]]   # [B, T, H, D]
                v_eff = vc[src, :, jnp.arange(T)[None, :]]
                kd = jnp.swapaxes(k_eff, 1, 2)               # [B, H, T, D]
                vd = jnp.swapaxes(v_eff, 1, 2)
            else:
                kd, vd = kc, vc
            scores = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32),
                                kd.astype(jnp.float32)) * (D ** -0.5)
            if smask is not None:
                m = smask.astype(jnp.float32).reshape(B, 1, -1)
                if m.shape[-1] < T:
                    m = jnp.pad(m, ((0, 0), (0, 0), (0, T - m.shape[-1])))
                scores = scores + m[..., :T]
            valid = jnp.arange(T)[None, None, :] <= lens[:, None, None]
            scores = jnp.where(valid, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bht,bhtd->bhd", probs,
                             vd.astype(jnp.float32))
        else:
            out = decode_attention(q, jnp.swapaxes(kc, 1, 2),
                                   jnp.swapaxes(vc, 1, 2), lens + 1)
        out = out.reshape(B, H * D)
        if oshift is not None:
            # reference MMHAStore<T, T, true>: (out + shift) * smooth,
            # per output channel
            out = (out.astype(jnp.float32)
                   + oshift.astype(jnp.float32).reshape(-1)[None, :]) \
                * osmooth.astype(jnp.float32).reshape(-1)[None, :]
        if quant_out:
            # reference QuantHelperFunc: clip(round(max_bound * scale *
            # v)) -> int8; round_type 0 = ties-to-even, 1 = half-away
            qv = quant_max_bound * out_scale * out.astype(jnp.float32)
            qv = jnp.rint(qv) if quant_round_type == 0 else \
                jnp.sign(qv) * jnp.floor(jnp.abs(qv) + 0.5)
            out = jnp.clip(qv, quant_min_bound, quant_max_bound).astype(
                jnp.int8)
        else:
            out = out.astype(cache.dtype)
        return out, jnp.stack([kc, vc])

    res = run_op("masked_multihead_attention", impl,
                 (x, cache_kv, bias, sequence_lengths, rotary_tensor,
                  src_mask, beam_cache_offset, qkv_out_scale, out_shift,
                  out_smooth), {}, differentiable=False)
    if beam_cache_offset is not None:
        # reference returns beam_cache_offset_out (inplace passthrough)
        return res[0], res[1], beam_cache_offset
    return res


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                            linear_weights, linear_biases, ffn_ln_scales,
                            ffn_ln_biases, ffn1_weights, ffn1_biases,
                            ffn2_weights, ffn2_biases, pre_layer_norm=True,
                            epsilon=1e-5, cache_kvs=None, pre_caches=None,
                            rotary_embs=None, time_step=None, attn_mask=None,
                            dropout_rate=0.0, rotary_emb_dims=0,
                            activation="gelu", training=False,
                            mode="upscale_in_train", trans_qkvw=True,
                            ring_id=-1, name=None):
    """Whole-stack fused transformer (reference
    incubate/nn/functional/fused_transformer.py fused_multi_transformer →
    fused_multi_transformer_op.cu).  N pre/post-LN blocks in one op:
    [LN →] fused-QKV → attention (flash for context, MMHA decode-step when
    ``time_step`` is set) → out-proj → +residual → [LN →] ffn1 → act →
    ffn2 → +residual.

    The reference hand-fuses this chain into one CUDA kernel per block;
    under XLA one traced op body compiles to the same fusion, and the layer
    loop is a static Python loop so each block inlines.  Decode mode
    scatters into the caller's preallocated ``cache_kvs``
    ([2, B, H, T_max, D] per layer) and returns (out, updated_caches).

    qkv_weight layout: [3, H, D, E] when ``trans_qkvw`` (reference default)
    else [E, 3, H, D].
    """
    from ....nn import functional as F
    from ....ops.pallas.decode_attention import decode_attention

    # pre_caches (prefix-tuning prompts, [2, B, H, P, D] per layer):
    # context phase — queries attend to prefix + causal-current, and the
    # prefix KV is written into cache_kvs ahead of the context KV
    # (reference fused_multi_transformer_op.cu:199-277 cache_offset).
    # Decode phase — RE-PASS pre_caches every step (the reference API
    # shape): ``time_step`` counts context + generated tokens EXCLUDING
    # the prefix, and the write slot is time_step + P.  Omitting
    # pre_caches on decode after a prefixed context call would scatter
    # into the middle of the filled cache, so P is rederived from the
    # argument each call rather than guessed.
    pres = list(pre_caches) if pre_caches is not None else None
    if dropout_rate and training:
        raise NotImplementedError(
            "fused_multi_transformer: training-mode dropout not "
            "implemented (the op is a serving path; reference defaults "
            "dropout_rate=0)")
    decode = time_step is not None
    t_step = int(getattr(time_step, "_value", time_step)) if decode else None
    n_layers = len(qkv_weights)
    caches = list(cache_kvs) if cache_kvs is not None else None
    rot = None
    if rotary_embs is not None:
        rot = jnp.asarray(getattr(rotary_embs, "_value", rotary_embs))

    def _ln(y, s, b):
        mu = jnp.mean(y, -1, keepdims=True)
        var = jnp.var(y, -1, keepdims=True)
        y = (y - mu) * jax.lax.rsqrt(var + epsilon)
        if s is not None:
            y = y * s
        if b is not None:
            y = y + b
        return y

    def impl(xv, mask, rot, *flat):
        it = iter(flat)

        def nxt():
            return next(it)

        lns, lnb = [nxt() for _ in range(n_layers)], \
            [nxt() for _ in range(n_layers)]
        qkvw = [nxt() for _ in range(n_layers)]
        qkvb = [nxt() for _ in range(n_layers)]
        lw = [nxt() for _ in range(n_layers)]
        lb = [nxt() for _ in range(n_layers)]
        flns = [nxt() for _ in range(n_layers)]
        flnb = [nxt() for _ in range(n_layers)]
        f1w = [nxt() for _ in range(n_layers)]
        f1b = [nxt() for _ in range(n_layers)]
        f2w = [nxt() for _ in range(n_layers)]
        f2b = [nxt() for _ in range(n_layers)]
        kv = [nxt() for _ in range(n_layers)] if caches is not None else \
            [None] * n_layers
        pc = [nxt() for _ in range(n_layers)] if pres is not None else \
            [None] * n_layers

        B, S, E = xv.shape
        new_caches = []
        y = xv
        for i in range(n_layers):
            w = qkvw[i]
            if trans_qkvw:
                H, D = w.shape[1], w.shape[2]
            else:
                H, D = w.shape[2], w.shape[3]
                w = jnp.transpose(w, (1, 2, 3, 0))
            resid = y
            h = _ln(y, lns[i], lnb[i]) if pre_layer_norm else y
            qkv = jnp.einsum("bse,thde->bsthd", h, w)
            if qkvb[i] is not None:
                qkv = qkv + qkvb[i][None, None]
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,S,H,D]
            if rot is not None:
                # rotary_embs: [2, B, 1, S_max, D] (cos, sin) — reference
                # fused_multi_transformer neox-half rotation on q/k
                pos0 = t_step if decode else 0
                cos = jax.lax.dynamic_slice_in_dim(rot[0], pos0, S,
                                                   axis=2)[:, 0][:, :, None]
                sin = jax.lax.dynamic_slice_in_dim(rot[1], pos0, S,
                                                   axis=2)[:, 0][:, :, None]

                def _rot_half(t):
                    t1, t2 = jnp.split(t, 2, axis=-1)
                    return jnp.concatenate([-t2, t1], axis=-1)

                q = q * cos + _rot_half(q) * sin
                k = k * cos + _rot_half(k) * sin
            if decode:
                # cache slot = prefix length + t_step (prefix KV occupies
                # cache[:P] from the context phase); RoPE position above
                # stays t_step — prefix prompts carry no positions
                # (reference fused_multi_transformer_op.cu: out_seq_len =
                # seq + cache_offset while rotary indexes the timestep)
                P_dec = pc[i].shape[3] if pc[i] is not None else 0
                slot = t_step + P_dec
                lens = jnp.full((B,), slot, jnp.int32)
                bidx = jnp.arange(B)
                kc = kv[i][0].at[bidx, :, slot].set(k[:, 0])
                vc = kv[i][1].at[bidx, :, slot].set(v[:, 0])
                new_caches.append(jnp.stack([kc, vc]))
                attn = decode_attention(q[:, 0], jnp.swapaxes(kc, 1, 2),
                                        jnp.swapaxes(vc, 1, 2), lens + 1)
                attn = attn[:, None]                       # [B, 1, H, D]
            else:
                k_full, v_full, amask = k, v, mask
                if pc[i] is not None:
                    pk = jnp.swapaxes(pc[i][0], 1, 2)   # [B, P, H, D]
                    pv = jnp.swapaxes(pc[i][1], 1, 2)
                    P = pk.shape[1]
                    k_full = jnp.concatenate([pk.astype(k.dtype), k], 1)
                    v_full = jnp.concatenate([pv.astype(v.dtype), v], 1)
                    if amask is None:
                        # prefix always visible; causal over current
                        amask = jnp.tril(
                            jnp.ones((S, P + S), bool), P)[None, None]
                    elif amask.shape[-1] == S:
                        # caller mask sized for the current tokens only:
                        # extend with an always-visible prefix band
                        if amask.dtype == jnp.bool_:
                            band = jnp.ones(
                                (*amask.shape[:-1], P), jnp.bool_)
                        else:
                            band = jnp.zeros(
                                (*amask.shape[:-1], P), amask.dtype)
                        amask = jnp.concatenate([band, amask], -1)
                if kv[i] is not None:
                    Tfill = k_full.shape[1]
                    kc = kv[i][0].at[:, :, :Tfill].set(
                        jnp.swapaxes(k_full, 1, 2))
                    vc = kv[i][1].at[:, :, :Tfill].set(
                        jnp.swapaxes(v_full, 1, 2))
                    new_caches.append(jnp.stack([kc, vc]))
                att = F.scaled_dot_product_attention(
                    q, k_full, v_full, attn_mask=amask,
                    is_causal=amask is None, training=False)
                attn = jnp.asarray(getattr(att, "_value", att))
            out = attn.reshape(B, S, H * D) @ lw[i]
            if lb[i] is not None:
                out = out + lb[i]
            y = resid + out
            if not pre_layer_norm:
                y = _ln(y, lns[i], lnb[i])
            resid = y
            h = _ln(y, flns[i], flnb[i]) if pre_layer_norm else y
            h = h @ f1w[i]
            if f1b[i] is not None:
                h = h + f1b[i]
            h = getattr(jax.nn, activation)(h)
            h = h @ f2w[i]
            if f2b[i] is not None:
                h = h + f2b[i]
            y = resid + h
            if not pre_layer_norm:
                y = _ln(y, flns[i], flnb[i])
        return (y, *new_caches) if new_caches else y

    flat_args = (list(ln_scales) + list(ln_biases) + list(qkv_weights)
                 + list(qkv_biases) + list(linear_weights)
                 + list(linear_biases) + list(ffn_ln_scales)
                 + list(ffn_ln_biases) + list(ffn1_weights)
                 + list(ffn1_biases) + list(ffn2_weights)
                 + list(ffn2_biases))
    if caches is not None:
        flat_args += caches
    if pres is not None:
        flat_args += pres
    out = run_op("fused_multi_transformer", impl,
                 (x, attn_mask, rot, *flat_args), {}, differentiable=False)
    if caches is not None:
        return out[0], list(out[1:])
    return out


def fused_conv_bn_act(x, conv_weight, bn_scale, bn_bias, bn_mean, bn_var,
                      stride=1, padding=0, epsilon=1e-5,
                      act: str = "relu", data_format="NCHW"):
    """Fused conv + batch-norm (inference stats) + activation (reference:
    phi/kernels/fusion/gpu/fused_scale_bias_relu_conv_bn_kernel.cu).

    TPU-native: BN folds INTO the conv weights algebraically —
    w' = w * scale/sqrt(var+eps) per out-channel, b' = bias - mean*scale/
    sqrt(var+eps) — so the whole op is ONE conv plus a bias-activation
    epilogue XLA fuses; no separate normalization pass ever runs."""
    from ....nn import functional as F

    def impl(w, sc, bb, mu, var):
        inv = sc * jax.lax.rsqrt(var + epsilon)
        w_f = w * inv[:, None, None, None]            # fold into OIHW
        b_f = bb - mu * inv
        return w_f, b_f

    # x is NOT an input of the fold — keeping it out of the op keys the
    # jit cache on the (tiny) weight shapes only, not the batch shape
    w_f, b_f = run_op("conv_bn_fold", impl,
                      (conv_weight, bn_scale, bn_bias, bn_mean, bn_var),
                      {})
    out = F.conv2d(x, w_f, bias=b_f, stride=stride, padding=padding,
                   data_format=data_format)
    if act == "relu":
        from ....ops import api as _api
        out = _api.relu(out)
    elif act not in (None, "identity", "none"):
        raise ValueError(f"unsupported act {act!r}")
    return out


def fused_adam(params, grads, lrs, moments1, moments2, beta1_pows,
               beta2_pows, master_weights=None, skip_update=None,
               beta1=0.9, beta2=0.999, epsilon=1e-8,
               multi_precision=False, use_adamw=False, weight_decay=0.01):
    """Multi-tensor Adam (reference phi/kernels/fused_adam_kernel.h): one
    fused update over a list of params, following the reference contract:
    ``beta1_pows``/``beta2_pows`` hold beta^t (bias correction divides by
    ``1 - pow``) and are RETURNED advanced by one factor; with
    ``master_weights`` the update runs on the fp32 master and the param
    gets the cast-down copy.

    Returns (params, moments1, moments2, beta1_pows, beta2_pows,
    master_weights)."""
    n = len(params)

    def pick(seq, i):
        return seq[i] if isinstance(seq, (list, tuple)) else seq

    outs = ([], [], [], [], [], [])
    for i in range(n):
        if skip_update is not None and bool(
                np.asarray(getattr(skip_update[i], "_value",
                                   skip_update[i]))):
            outs[0].append(params[i])
            outs[1].append(moments1[i])
            outs[2].append(moments2[i])
            outs[3].append(pick(beta1_pows, i))
            outs[4].append(pick(beta2_pows, i))
            outs[5].append(None if master_weights is None
                           else master_weights[i])
            continue

        def impl(pv, gv, m1v, m2v, b1p, b2p, lr, mw):
            g32 = gv.astype(jnp.float32)
            work = mw if mw is not None else pv.astype(jnp.float32)
            if use_adamw:
                work = work * (1.0 - lr * weight_decay)
            nm1 = beta1 * m1v + (1 - beta1) * g32
            nm2 = beta2 * m2v + (1 - beta2) * g32 * g32
            mhat = nm1 / (1 - b1p)            # pows hold beta^t already
            vhat = nm2 / (1 - b2p)
            new_work = work - lr * mhat / (jnp.sqrt(vhat) + epsilon)
            return (new_work.astype(pv.dtype), nm1, nm2,
                    b1p * beta1, b2p * beta2,
                    new_work if mw is not None else None)

        mw = None if master_weights is None else master_weights[i]
        res = run_op("fused_adam", impl,
                     (params[i], grads[i], moments1[i], moments2[i],
                      pick(beta1_pows, i), pick(beta2_pows, i),
                      pick(lrs, i), mw), {})
        for acc, v in zip(outs, res):
            acc.append(v)
    return outs


def fused_matmul_bias(x, y, bias=None, transpose_x=False,
                      transpose_y=False, name=None):
    """matmul + bias epilogue (reference fused_matmul_bias →
    fused_gemm_epilogue cublasLt kernel; XLA fuses the epilogue natively)."""
    def impl(xv, yv, b):
        a = jnp.swapaxes(xv, -1, -2) if transpose_x else xv
        w = jnp.swapaxes(yv, -1, -2) if transpose_y else yv
        out = a @ w
        return out if b is None else out + b
    return run_op("fused_matmul_bias", impl, (x, y, bias), {})


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1,
                      add_residual=True, name=None):
    """Transformer FFN block as one fused region (reference
    incubate/nn/functional/fused_transformer.py:36 →
    fused_feedforward kernel): [pre-]LN → linear1 → act → dropout →
    linear2 → dropout → residual [→ post-LN]."""
    from ....core.rng import next_rng_key
    keys = (next_rng_key(), next_rng_key()) if (
        training and (dropout1_rate or dropout2_rate)) else (None, None)

    def ln(v, scale, b, eps):
        mu = v.mean(-1, keepdims=True)
        var = ((v - mu) ** 2).mean(-1, keepdims=True)
        out = (v - mu) * jax.lax.rsqrt(var + eps)
        if scale is not None:
            out = out * scale
        if b is not None:
            out = out + b
        return out

    def drop(v, rate, key):
        if rate == 0.0:
            return v
        if not training or key is None:
            # downscale_in_infer applies the (1-p) factor at INFERENCE
            # (reference nn/functional/common.py dropout mode semantics)
            return v * (1.0 - rate) if mode == "downscale_in_infer" else v
        keep = jax.random.bernoulli(key, 1.0 - rate, v.shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - rate), 0.0)
        return jnp.where(keep, v, 0.0)

    acts = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}
    if activation not in acts:
        raise ValueError(f"unsupported activation {activation!r}")

    def impl(xv, w1, w2, b1, b2, s1, lb1, s2, lb2, k1, k2):
        h = ln(xv, s1, lb1, ln1_epsilon) if pre_layer_norm else xv
        h = h @ w1
        if b1 is not None:
            h = h + b1
        h = acts[activation](h)
        h = drop(h, dropout1_rate, k1)
        h = h @ w2
        if b2 is not None:
            h = h + b2
        h = drop(h, dropout2_rate, k2)
        out = xv + h if add_residual else h
        if not pre_layer_norm:
            out = ln(out, s2, lb2, ln2_epsilon)
        return out.astype(xv.dtype)

    return run_op("fused_feedforward", impl,
                  (x, linear1_weight, linear2_weight, linear1_bias,
                   linear2_bias, ln1_scale, ln1_bias, ln2_scale, ln2_bias,
                   keys[0], keys[1]), {})


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size=None,
                     name=None):
    """Max enc/dec lengths for block attention scheduling (reference
    fusion/gpu blha_get_max_len kernel)."""
    def impl(enc, dec):
        return jnp.max(enc), jnp.max(dec)
    return run_op("blha_get_max_len", impl,
                  (seq_lens_encoder, seq_lens_decoder), {})


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_encoder,
                              seq_lens_decoder, seq_lens_this_time,
                              padding_offsets=None, cum_offsets=None,
                              cu_seqlens_q=None, cu_seqlens_k=None,
                              block_tables=None, *, max_seq_len=None,
                              block_size=None, use_neox_style=False,
                              name=None, **kw):
    """Paged-KV block attention, decode phase (reference
    fusion/gpu/block_multi_head_attention_kernel.cu).

    TPU scope: the decode step over a paged pool — qkv [B, 3, H, D] (one
    new token per sequence), caches are page pools [NB, BS, H, D],
    block_tables [B, MB].  Appends the new K/V to the pages, then runs
    the paged gather + masked attention (ops/paged_kv.py).  Returns
    (out [B, H, D], key_cache, value_cache)."""
    from ....ops.paged_kv import paged_append, paged_decode_attention
    bs = block_size or key_cache.shape[1] if hasattr(
        key_cache, "shape") else block_size

    def impl(p, kc, vc, dec_lens, bt):
        q, k_new, v_new = p[:, 0], p[:, 1], p[:, 2]
        kc, vc = paged_append(kc, vc, k_new, v_new, bt, dec_lens,
                              int(bs))
        out = paged_decode_attention(q, kc, vc, bt, dec_lens + 1)
        return out, kc, vc

    # inference-only, as in the reference (no grad kernel): the page
    # walk's trip count is data, which reverse mode cannot unroll
    return run_op("block_multihead_attention", impl,
                  (qkv, key_cache, value_cache, seq_lens_decoder,
                   block_tables), {}, differentiable=False)


def variable_length_memory_efficient_attention(query, key, value,
                                               seq_lens, kv_seq_lens,
                                               mask=None, scale=None,
                                               causal=False,
                                               pre_cache_length=0):
    """Varlen memory-efficient attention (reference fusion/gpu
    variable_length_memory_efficient_attention + cutlass): per-sequence
    lengths mask a padded batch; the flash kernel path gives O(T)
    memory, the dense fallback masks explicitly.  q/k/v: [B, H, S, D];
    seq_lens/kv_seq_lens: [B]."""
    import math as _math

    def impl(q, k, v, ql, kl, m):
        B, H, S, D = q.shape
        s = scale if scale is not None else 1.0 / _math.sqrt(D)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * s
        kmask = jnp.arange(k.shape[2])[None, None, None, :] \
            < kl[:, None, None, None]
        qmask = jnp.arange(S)[None, None, :, None] < ql[:, None, None, None]
        mask_all = kmask & qmask
        if causal:
            # query i may see the full pre-cache prefix plus keys up to
            # its own (cache-offset) position
            rows = jnp.arange(S)[:, None] + int(pre_cache_length)
            tri = rows >= jnp.arange(k.shape[2])[None, :]
            mask_all = mask_all & tri[None, None]
        logits = jnp.where(mask_all, logits, jnp.finfo(jnp.float32).min)
        if m is not None:
            logits = logits + m.astype(jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        p = jnp.where(mask_all, p, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(jnp.float32)).astype(q.dtype)

    return run_op("var_len_mem_efficient_attention", impl,
                  (query, key, value, seq_lens, kv_seq_lens, mask), {})


def fused_moe(x, gate_weight, expert_weights1, expert_biases1,
              expert_weights2, expert_biases2, *, moe_topk=2,
              norm_topk_prob=True, name=None, **kw):
    """Fused MoE FFN (reference incubate fused_moe → fused_moe kernel):
    softmax gate → top-k dispatch → per-expert FFN → weighted combine.
    Dense einsum formulation — every token visits every expert and the
    top-k mask zeroes the rest, which on TPU trades FLOPs for zero
    all-to-all and perfect load balance at small expert counts."""
    def impl(xv, gw, w1, b1, w2, b2):
        B = xv.shape[:-1]
        d = xv.shape[-1]
        t = xv.reshape(-1, d)                      # [T, d]
        gate = jax.nn.softmax(t @ gw, axis=-1)     # [T, E]
        E = gate.shape[-1]
        topv, topi = jax.lax.top_k(gate, moe_topk)
        if norm_topk_prob:
            topv = topv / jnp.sum(topv, -1, keepdims=True)
        w_dense = jnp.zeros((t.shape[0], E), gate.dtype)
        w_dense = w_dense.at[jnp.arange(t.shape[0])[:, None],
                             topi].set(topv)
        h = jnp.einsum("td,edf->tef", t, w1)
        if b1 is not None:
            h = h + b1[None]
        h = jax.nn.gelu(h)
        h = jnp.einsum("tef,efd->ted", h, w2)
        if b2 is not None:
            h = h + b2[None]
        out = jnp.einsum("ted,te->td", h, w_dense)
        return out.reshape(*B, d).astype(xv.dtype)

    return run_op("fused_moe", impl,
                  (x, gate_weight, expert_weights1, expert_biases1,
                   expert_weights2, expert_biases2), {})


def fused_ec_moe(x, gate, expert_weights1, expert_biases1, expert_weights2,
                 expert_biases2, act_type="gelu", name=None):
    """Expert-choice MoE (reference fused_ec_moe kernel): same fused
    dense formulation with a precomputed gate tensor."""
    def impl(xv, g, w1, b1, w2, b2):
        B = xv.shape[:-1]
        d = xv.shape[-1]
        t = xv.reshape(-1, d)
        gate_p = jax.nn.softmax(g.reshape(t.shape[0], -1), axis=-1)
        h = jnp.einsum("td,edf->tef", t, w1) + (
            b1[None] if b1 is not None else 0.0)
        h = jax.nn.gelu(h) if act_type == "gelu" else jax.nn.relu(h)
        h = jnp.einsum("tef,efd->ted", h, w2) + (
            b2[None] if b2 is not None else 0.0)
        out = jnp.einsum("ted,te->td", h, gate_p)
        return out.reshape(*B, d).astype(xv.dtype)

    return run_op("fused_ec_moe", impl,
                  (x, gate, expert_weights1, expert_biases1,
                   expert_weights2, expert_biases2), {})
