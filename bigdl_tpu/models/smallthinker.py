"""SmallThinker (`smallthinker`, e.g. PowerInfer/SmallThinker-21BA3B-Instruct):
window and full attention mixed BY LAYER, a rope in the window layers only,
and in every layer top-k ReLU-gated experts routed from the layer's INPUT.

With x the input of layer l (the residual stream):

    r = W_r x                     router logits, float32, from x BEFORE the
                                  attention norm
    I = top-k of softmax(r);  w = softmax(r)[I] renormalised over the k
    a = rmsnorm(x);  q, k, v = W_q a, W_k a, W_v a         (no biases)
    rope_layers[l]:     q, k = rope(q, k)   (half-split)   else NO rope call
    sliding_layers[l]:  causal attention over the last `sliding_window`
                        positions;                         else over all
    h = x + W_o attention(q, k, v)
    m = rmsnorm(h)
    out = h + sum over e in I of w_e W_down^e (relu(W_gate^e m) * (W_up^e m))
    logits = W_head rmsnorm(x_L)                           (untied)

The published layouts are one period repeated (`[0, 1, 1, 1]` x 13: a full
layer without positions, then three window layers with a rope). `forward`
finds the period and SCANS over the periods with the period's layers as the
body, so a layer body is traced once a position of the period and not once
a layer. `params["period"]["0"]`, `["1"]`, ... stack each position's layers
over the periods (`[n_periods, ...]`; a dict and not a list: `save_low_bit`
walks dicts). Packed codes reach their kernels by the period's index out of
the unsliced stacks (`linear(layer=)`, `_moe_dispatch(layer=)`), as in
`llama.forward`.

The cache is `kvwindow.PageGroups`: the full layers' keys and values in a
GLOBAL group, the window layers' in a WINDOW group whose pages the serving
engine frees behind the window. `InferenceEngine(paged=True)` gets the paged
form from `init_paged_cache` (through its kind, `kvwindow.CACHE_KIND`) and
prefills on the dense form of ONE row; `TpuModel.generate` gets the
dense form from `init_cache` (every position kept, the window a mask).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvcache, kvpaged, kvwindow
from bigdl_tpu.models import llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.linear import stacks_in

Params = dict[str, Any]

_ATTN_STACKS = ("wq", "wk", "wv", "wo")
_QUANT_TARGETS = _ATTN_STACKS + llama._EXPERT_STACKS


def layouts(config: ModelConfig) -> tuple[tuple, tuple]:
    """(window or not, rope or not) of every layer."""
    L = config.num_hidden_layers
    sliding = tuple(bool(config.layer_is_sliding(l)) for l in range(L))
    rope = tuple(bool(r) for r in (config.rope_layers or (1,) * L))
    return sliding, rope


def period(config: ModelConfig) -> int:
    """Layers of the shortest pattern that the two layouts repeat."""
    sliding, rope = layouts(config)
    kinds = list(zip(sliding, rope))
    L = len(kinds)
    return next(p for p in range(1, L + 1)
                if L % p == 0 and kinds == kinds[:p] * (L // p))


def group_layers(config: ModelConfig) -> tuple[int, int]:
    """(full layers, window layers): the two groups' depths."""
    sliding, _ = layouts(config)
    return len(sliding) - sum(sliding), sum(sliding)


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                scale: float = 0.02) -> Params:
    """Random init (tests/benchmarks run without checkpoints)."""
    hid, V = config.hidden_size, config.vocab_size
    E, I = config.num_experts, config.moe_intermediate_size
    QD, KD = config.q_dim, config.kv_dim
    P = period(config)
    n = config.num_hidden_layers // P
    keys = iter(jax.random.split(key, 8 * P + 2))

    def w(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def position():
        return {"attn_norm": jnp.ones((n, hid), dtype),
                "mlp_norm": jnp.ones((n, hid), dtype),
                "wq": w((n, QD, hid)), "wk": w((n, KD, hid)),
                "wv": w((n, KD, hid)), "wo": w((n, hid, QD)),
                "router": w((n, E, hid)), "w_gate_e": w((n, E, I, hid)),
                "w_up_e": w((n, E, I, hid)), "w_down_e": w((n, E, hid, I))}

    params: Params = {"embed": w((V, hid)),
                      "period": {str(j): position() for j in range(P)},
                      "final_norm": jnp.ones((hid,), dtype)}
    if not config.tie_word_embeddings:
        params["lm_head"] = w((V, hid))
    return params


def quantize_params(params: Params, qtype: str,
                    lm_head_qtype: Optional[str] = None) -> Params:
    """Pack the projections, the experts and the head; the router and the
    norms stay as they are."""
    from bigdl_tpu.quant import QTensor, quantize_or_dense
    from bigdl_tpu.quant.qtypes import resolve_qtype, split_mixed_qtype

    qtype, head_default = split_mixed_qtype(qtype)
    lm_head_qtype = lm_head_qtype or head_default
    spec = resolve_qtype(qtype)
    if spec.is_dense:
        return params
    out = dict(params)
    out["period"] = {
        j: {name: quantize_or_dense(w, spec.name, name)
            if name in _QUANT_TARGETS and not isinstance(w, QTensor) else w
            for name, w in stack.items()}
        for j, stack in params["period"].items()}
    lm_spec = resolve_qtype(lm_head_qtype) if lm_head_qtype else spec
    head = params.get("lm_head")
    if head is not None and not isinstance(head, QTensor) \
            and not lm_spec.is_dense:
        out["lm_head"] = quantize_or_dense(head, lm_spec.name, "lm_head")
    return out


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_paged_cache(config: ModelConfig, n_pages: int, page_size: int,
                     batch: int, max_pages_per_row: int
                     ) -> kvwindow.PageGroups:
    """The family's PAGED cache for `InferenceEngine(paged=True)`: a global
    group of `n_pages` for the full layers and a window group, sized from
    the slots and the window, for the rest (`kvwindow`)."""
    n_full, n_window = group_layers(config)
    return kvwindow.init_groups(
        n_full, n_window, n_pages, page_size, config.num_key_value_heads,
        config.head_dim_, batch, max_pages_per_row, config.sliding_window)


PAGED_CACHE_KIND = kvwindow.KIND


def init_cache(config: ModelConfig, batch: int, cache_len: int = 0,
               quantize_kv: bool = False,
               dtype=jnp.bfloat16) -> kvwindow.PageGroups:
    """`generate_tokens`' family hook: the dense form."""
    if quantize_kv:
        raise NotImplementedError(
            f"quantize_kv is not available for {kvwindow.KIND} "
            f"({config.model_type}): fp8 pages in two groups are not wired")
    return kvwindow.init_dense(
        *group_layers(config), batch, cache_len, config.num_key_value_heads,
        config.head_dim_, dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _keep_codes_out(stack: Params) -> tuple[Params, dict]:
    """`granitemoehybrid._keep_codes_out` for a position's stack: the packed
    codes of every weight that goes to a kernel taken out of what the scan
    slices; the body hands the whole stack back with the period's index."""
    from bigdl_tpu.ops.linear import grouped_route, stacks_out
    from bigdl_tpu.quant import QTensor

    names = [n for n in _ATTN_STACKS
             if isinstance(stack[n], QTensor)
             and grouped_route(stack[n]) is None]
    if isinstance(stack["w_up_e"], QTensor) \
            and llama.moe_grouped_why_not(stack, False) is None:
        names += list(llama._EXPERT_STACKS)
    return stacks_out(stack, names)


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[kvwindow.PageGroups],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
    moe_routing: bool = False,  # static: also return every layer's top-k
    # expert ids [L, B, T, k] int32, in the model's layer order
    logits_at=None,  # traced position: the head on that one position only
    # (the engine's prefill pads a bucket on the right and wants the last
    # TOKEN's logits: [T, V] at T = 8192 is 2.5 GB and a fifth of the FLOPs)
):
    """Returns (logits [B, T, V] float32, the cache with `pos` advanced),
    and the routing third when asked. `cache` None runs a whole sequence
    from nothing and keeps nothing."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas
    from bigdl_tpu.ops.rope import (apply_rotary_emb, make_inv_freq_scaled,
                                    rope_cos_sin)

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    Hq, Hkv, D = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim_)
    eps, W = config.rms_norm_eps, config.sliding_window
    sliding, rope = layouts(config)
    P = period(config)
    n_periods = config.num_hidden_layers // P
    # a layer's index in its group: the period's, times the group's layers
    # a period, plus its rank among them
    per = (P - sum(sliding[:P]), sum(sliding[:P]))
    rank = [sum(s == sliding[j] for s in sliding[:j]) for j in range(P)]

    fresh = cache is None
    if fresh:
        with scope("engine"):
            cache = init_cache(config, B, T, dtype=compute_dtype)
    paged = cache.paged
    scalar_pos = cache.pos.ndim == 0
    with scope("engine"):
        pos_col = cache.pos if scalar_pos else cache.pos[:, None]
        slots = pos_col + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B|1, T]

    detail = f"mode={mode} B{B} T{T}"
    note = (f"full x{per[0]} nope, window {W} x{per[1]} rope, "
            f"{n_periods} periods")
    use_kernel = paged and mode == "decode" and T == 1 and use_pallas()
    use_flash = (not paged and mode == "prefill" and T > 1 and scalar_pos
                 and use_pallas())
    if use_kernel:
        routes.note("attention", "pallas:paged", f"{detail} {note}")
        with scope("attn"):
            row_live = kvpaged.live_rows(cache)
    elif use_flash:
        routes.note("attention", "pallas:flash", f"{detail} {note}")
    else:
        why = why_not_pallas() or (
            "a paged or per-row cache at T > 1: flash takes one dense row"
            if T > 1 else "dense-cache decode: fused XLA attention")
        routes.note("attention", "xla", f"{detail} {note} ({why})")
        with scope("attn"):  # the masks, once for every layer
            sj = jnp.arange(cache.max_len)[None, None, :]
            full = (sj <= slots[..., None]) & (
                sj >= cache.start[:, None, None])
            masks = (full[:, None, None],  # [B, 1, 1, T, S]
                     (full & (sj > slots[..., None] - W))[:, None, None]
                     if W else None)

    with scope("attn.rope"):  # the tables, once for every layer
        inv_freq, att_scale = make_inv_freq_scaled(
            config.rotary_dim, config.rope_theta, config.rope_scaling_dict,
            seq_len=cache.max_len)
        cos, sin = rope_cos_sin(cache.group(False).next_positions(T),
                                inv_freq, scale=att_scale)

    with scope("engine"):
        h = llama.embed_tokens(config, params, tokens, compute_dtype)

    def layer(j, hidden, c, p, codes, i):
        """Layer `j` of period `i`."""
        p = stacks_in(p, codes)
        window = W if sliding[j] else None
        with scope("engine"):  # the loop's own counts
            idx = i * per[sliding[j]] + rank[j]

        def proj(x, name):
            return linear(x, p[name], None, compute_dtype,
                          layer=i if name in codes else None)

        with scope("moe.router"):  # from the layer's INPUT
            topv, topi = llama._moe_router(config, hidden, p)
        with scope("norm"):
            x = rms_norm(hidden, p["attn_norm"], eps)
        with scope("attn.proj"):
            q = proj(x, "wq").reshape(B, T, Hq, D)
            k = proj(x, "wk").reshape(B, T, Hkv, D)
            v = proj(x, "wv").reshape(B, T, Hkv, D)
        if rope[j]:  # a NoPE layer makes no rope call at all
            with scope("attn.rope"):
                q, k = apply_rotary_emb(q, k, cos, sin)
        with scope("attn"):
            g = kvcache.update_layer(c.group(sliding[j]), idx, k, v)
            c = c.with_group(sliding[j], g)
            if use_kernel:
                from bigdl_tpu.ops.pallas import paged_decode_attention

                out = paged_decode_attention(
                    q[:, 0], g.k, g.v, g.block_tables, idx, c.pos, c.start,
                    window=window, live=row_live)[:, None]
            else:
                kf, vf = kvcache.read_layer(g, idx, compute_dtype)
                if use_flash:
                    from bigdl_tpu.ops.pallas import flash_attention

                    out = flash_attention(q, kf, vf, start=c.start,
                                          q_offset=c.pos, window=window)
                else:
                    out = attention(q, kf, vf, masks[sliding[j]])
        with scope("attn.proj"):
            out = proj(out.reshape(B, T, Hq * D).astype(compute_dtype), "wo")
        with scope("norm"):
            hidden = hidden + out
            x = rms_norm(hidden, p["mlp_norm"], eps).astype(compute_dtype)
        with scope("ffn"):
            d = llama._moe_dispatch(
                config, x, p, compute_dtype, topv, topi,
                layer=i if "w_up_e" in codes else None)
        with scope("norm"):  # the add fuses with the next norm
            return hidden + d, c, topi

    stacks = [_keep_codes_out(params["period"][str(j)]) for j in range(P)]

    def body(carry, xs):
        hidden, c, i = carry
        chosen = []
        for j, p in enumerate(xs):
            hidden, c, topi = layer(j, hidden, c, p, stacks[j][1], i)
            chosen.append(topi)
        with scope("engine"):  # the loop's own count, the ids for the host
            return (hidden, c, i + 1), (jnp.stack(chosen) if moe_routing
                                        else None)

    (h, cache, _), routing = jax.lax.scan(
        body, (h, cache, jnp.zeros((), jnp.int32)),
        tuple(sliced for sliced, _ in stacks))

    with scope("lm_head"):
        if logits_at is not None:
            h = jax.lax.dynamic_slice_in_dim(h, logits_at, 1, axis=1)
        elif last_logits_only:
            h = h[:, -1:]
        logits = llama.lm_head_logits(config, params, h, compute_dtype)
    with scope("engine"):
        extra = ()
        if moe_routing:  # [n_periods, P, B, T, k] -> the model's layer order
            extra = (routing.reshape((-1,) + routing.shape[2:]),)
        if fresh:
            return (logits, None) + extra
        return (logits, kvwindow.advance(cache, T)) + extra
