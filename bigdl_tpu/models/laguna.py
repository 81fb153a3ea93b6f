"""Laguna (`laguna`, e.g. poolside/Laguna-XS.2): full and window attention
mixed BY LAYER, and the two kinds of layer differ in SHAPE: their query
heads, their rope and so their projections; every head's output passes a
sigmoid gate; a dense first layer, then sigmoid-routed experts and a shared
one.

With h the residual stream and x = rmsnorm(h), in layer l of kind
`layer_types[l]` with Hq(l) = `num_attention_heads_per_layer[l]` query heads
over the same Hkv KV heads of size D:

    q = W_q x  [Hq(l), D];  k, v = W_k x, W_v x  [Hkv, D]       (no biases)
    full layer:    q, k = rope(q, k) over the first `partial_rotary_factor`
                   of a head, YaRN-scaled frequencies, cos / sin times the
                   published `attention_factor`; the other lanes untouched
    window layer:  q, k = rope(q, k) over the whole head, theta
                   `rope_local_theta`, no scaling; causal attention over the
                   last `sliding_window` positions only
    a = softmax(q k^T / sqrt(D)) v                    Hq(l) / Hkv to a KV head
    g = sigmoid(W_g x)  [Hq(l)]     one scalar a head and token, from the
                                    SAME normed x; a_head *= g_head
    h = h + W_o concat(a);  y = rmsnorm(h)
    l < first_k_dense_replace:  h = h + W_d(silu(W_gate y) * (W_up y))
    else:  s = sigmoid(W_r y) float32;  I = top-k of s;
           w = s[I] / sum(s[I]) * routed_scaling_factor
           h = h + sum over e in I of w_e E_e(y) + S(y)      E_e, S SwiGLU,
                                    the shared S ungated at weight 1
    logits = W_head rmsnorm(h_L)                                   (untied)

The half-split (`rotate_half`) rope convention, the gate's per-head form and
the absence of a q/k norm are the model type's: config.json has no key for
them (bench/configs/laguna-xs.2-int4.json, `assumed`). The router is
`deepseek._router`'s sigmoid branch with one group and NO selection bias
(the tree has no `e_bias` leaf).

The published layouts are one period repeated ([full, window, window,
window]) and layer 0's feed-forward is dense, so the FIRST period is not the
scan's body: `params["first"]["0".."P-1"]` are its layers, each by itself,
run one after another; `params["period"]["0".."P-1"]` stack the other
periods' layers by POSITION (`[n_periods - 1, ...]`; the positions differ in
shape, so no one stack holds a period), and `forward` scans over those
periods with the period's layers as the body. No scan slices a larger
stack, and packed codes reach their kernels by the period's index out of
the unsliced stacks (`linear(layer=)`, `_moe_dispatch(layer=)`), as in
`models/smallthinker.py`, whose cache this family shares:
`kvwindow.PageGroups`, the full layers' keys and values in a GLOBAL group
of pages and the window layers' in a WINDOW group whose pages the serving
engine frees behind the window. Both groups keep Hkv heads of D, so both
pools are `[.., page, Hkv, D]`. `InferenceEngine(paged=True)` gets the paged
form from `init_paged_cache` (its kind, `kvwindow.CACHE_KIND`);
`TpuModel.generate` the dense form from `init_cache`.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvcache, kvpaged, kvwindow
from bigdl_tpu.models import deepseek, llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.linear import stacks_in

Params = dict[str, Any]

# what goes through `ops/linear`: the projections, layer 0's dense
# feed-forward and the shared expert
_LINEAR_STACKS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                  "w_gate_s", "w_up_s", "w_down_s")
_QUANT_TARGETS = _LINEAR_STACKS + llama._EXPERT_STACKS


def layouts(config: ModelConfig) -> tuple[tuple, tuple]:
    """(window or not, query heads) of every layer."""
    L = config.num_hidden_layers
    sliding = tuple(bool(config.layer_is_sliding(l)) for l in range(L))
    heads = tuple(config.heads_per_layer
                  or (config.num_attention_heads,) * L)
    return sliding, heads


def period(config: ModelConfig) -> int:
    """Layers of the shortest pattern that the two layouts repeat."""
    kinds = list(zip(*layouts(config)))
    L = len(kinds)
    return next(p for p in range(1, L + 1)
                if L % p == 0 and kinds == kinds[:p] * (L // p))


def group_layers(config: ModelConfig) -> tuple[int, int]:
    """(full layers, window layers): the two groups' depths."""
    sliding, _ = layouts(config)
    return len(sliding) - sum(sliding), sum(sliding)


def _check(config: ModelConfig) -> tuple[int, int]:
    """(the period, the periods after the first); refuses a layout whose
    dense layers do not end inside the first period."""
    P = period(config)
    n = config.num_hidden_layers // P
    if not 0 <= config.first_k_dense_replace <= P or n < 2:
        raise NotImplementedError(
            f"laguna with {config.first_k_dense_replace} leading dense "
            f"layers and {n} periods of {P}: the first period is run by "
            "itself and the others are scanned")
    return P, n - 1


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                scale: float = 0.02) -> Params:
    """Random init (tests/benchmarks run without checkpoints)."""
    hid, V, D = config.hidden_size, config.vocab_size, config.head_dim_
    E, I = config.num_experts, config.moe_intermediate_size
    S, F = config.shared_expert_intermediate_size, config.intermediate_size
    KD = config.kv_dim
    _, heads = layouts(config)
    P, n = _check(config)
    keys = iter(jax.random.split(key, 32 * P + 2))

    def w(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def position(j: int, lead: tuple, dense: bool) -> Params:
        Hq = heads[j]
        p = {"attn_norm": jnp.ones(lead + (hid,), dtype),
             "mlp_norm": jnp.ones(lead + (hid,), dtype),
             "wq": w(lead + (Hq * D, hid)), "wk": w(lead + (KD, hid)),
             "wv": w(lead + (KD, hid)), "wo": w(lead + (hid, Hq * D))}
        if config.attn_gate:
            p["attn_gate"] = w(lead + (Hq, hid))
        if dense:
            p.update(w_gate=w(lead + (F, hid)), w_up=w(lead + (F, hid)),
                     w_down=w(lead + (hid, F)))
            return p
        p.update(router=w(lead + (E, hid)),
                 w_gate_e=w(lead + (E, I, hid)), w_up_e=w(lead + (E, I, hid)),
                 w_down_e=w(lead + (E, hid, I)))
        if S:
            p.update(w_gate_s=w(lead + (S, hid)), w_up_s=w(lead + (S, hid)),
                     w_down_s=w(lead + (hid, S)))
        return p

    params: Params = {
        "embed": w((V, hid)),
        "first": {str(j): position(j, (), j < config.first_k_dense_replace)
                  for j in range(P)},
        "period": {str(j): position(j, (n,), False) for j in range(P)},
        "final_norm": jnp.ones((hid,), dtype)}
    if not config.tie_word_embeddings:
        params["lm_head"] = w((V, hid))
    return params


def quantize_params(params: Params, qtype: str,
                    lm_head_qtype: Optional[str] = None) -> Params:
    """Pack the projections, the dense and the shared feed-forward, the
    experts and the head; the router, the gate and the norms stay as they
    are."""
    from bigdl_tpu.quant import QTensor, quantize_or_dense
    from bigdl_tpu.quant.qtypes import resolve_qtype, split_mixed_qtype

    qtype, head_default = split_mixed_qtype(qtype)
    lm_head_qtype = lm_head_qtype or head_default
    spec = resolve_qtype(qtype)
    if spec.is_dense:
        return params
    out = dict(params)
    for part in ("first", "period"):
        out[part] = {
            j: {name: quantize_or_dense(w, spec.name, name)
                if name in _QUANT_TARGETS and not isinstance(w, QTensor)
                else w for name, w in stack.items()}
            for j, stack in params[part].items()}
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
    """`smallthinker._keep_codes_out` for a position's stack: the packed
    codes of every weight that goes to a kernel taken out of what the scan
    slices; the body hands the whole stack back with the period's index."""
    from bigdl_tpu.ops.linear import grouped_route, stacks_out
    from bigdl_tpu.quant import QTensor

    names = [n for n in _LINEAR_STACKS
             if isinstance(stack.get(n), QTensor)
             and grouped_route(stack[n]) is None]
    if isinstance(stack.get("w_up_e"), QTensor) \
            and llama.moe_grouped_why_not(stack, False) is None:
        names += list(llama._EXPERT_STACKS)
    return stacks_out(stack, names)


def _rope_tables(config: ModelConfig, cache, T: int):
    """(cos, sin) of the next `T` positions, for a full layer and for a
    window layer: two tables a forward."""
    from bigdl_tpu.ops.rope import (default_inv_freq, make_inv_freq_scaled,
                                    rope_cos_sin)

    D = config.head_dim_
    positions = cache.group(False).next_positions(T)
    inv, att = make_inv_freq_scaled(
        config.rotary_dim, config.rope_theta, config.rope_scaling_dict,
        seq_len=cache.max_len)
    local = int(D * (config.rope_local_partial_rotary_factor or 1.0))
    return (rope_cos_sin(positions, inv, scale=att),
            rope_cos_sin(positions, default_inv_freq(
                local - local % 2, config.rope_local_theta
                or config.rope_theta)))


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[kvwindow.PageGroups],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
    moe_routing: bool = False,  # static: also return every SPARSE layer's
    # top-k expert ids [L - first_k_dense_replace, B, T, k] int32, in the
    # model's layer order (the dense layers route nothing)
    logits_at=None,  # traced position: the head on that one position only
):
    """Returns (logits [B, T, V] float32, the cache with `pos` advanced),
    and the routing third when asked. `cache` None runs a whole sequence
    from nothing and keeps nothing."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas
    from bigdl_tpu.ops.rope import apply_rotary_emb

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    Hkv, D = config.num_key_value_heads, config.head_dim_
    eps, W = config.rms_norm_eps, config.sliding_window
    sliding, heads = layouts(config)
    P, n_scanned = _check(config)
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
    use_kernel = paged and mode == "decode" and T == 1 and use_pallas()
    use_flash = (not paged and mode == "prefill" and T > 1 and scalar_pos
                 and use_pallas())
    if use_kernel:
        route, why = "pallas:paged", ""
        with scope("attn"):
            row_live = kvpaged.live_rows(cache)
    elif use_flash:
        route, why = "pallas:flash", ""
    else:
        route = "xla"
        why = " (" + (why_not_pallas() or (
            "a paged or per-row cache at T > 1: flash takes one dense row"
            if T > 1 else "dense-cache decode: fused XLA attention")) + ")"
        with scope("attn"):  # the masks, once for every layer
            sj = jnp.arange(cache.max_len)[None, None, :]
            full = (sj <= slots[..., None]) & (
                sj >= cache.start[:, None, None])
            masks = (full[:, None, None],  # [B, 1, 1, T, S]
                     (full & (sj > slots[..., None] - W))[:, None, None]
                     if W else None)
    for s in sorted(set(sliding[:P])):  # a line each kind of layer
        j = sliding.index(s)
        kind = (f"window {W} x{per[1]} rope {config.rope_local_theta:g}"
                if s else
                f"full x{per[0]} yarn over {config.rotary_dim} of {D}")
        routes.note("attention", route,
                    f"{detail} {kind}, {heads[j]} heads on {Hkv}"
                    f"{', gated' if config.attn_gate else ''}, "
                    f"{n_scanned + 1} periods{why}")

    with scope("attn.rope"):
        tables = _rope_tables(config, cache, T)
    with scope("engine"):
        h = llama.embed_tokens(config, params, tokens, compute_dtype)

    def layer(j, hidden, c, p, codes, i, idx):
        """The layer at position `j` of a period: `i` the period's index in
        the stacks `codes` come from (None: `p` is the layer's own), `idx`
        the layer's index in its group of pages."""
        p = stacks_in(p, codes)
        Hq, window = heads[j], W if sliding[j] else None

        def proj(x, name):
            return linear(x, p[name], None, compute_dtype,
                          layer=i if name in codes else None)

        with scope("norm"):
            x = rms_norm(hidden, p["attn_norm"], eps)
        with scope("attn.proj"):
            q = proj(x, "wq").reshape(B, T, Hq, D)
            k = proj(x, "wk").reshape(B, T, Hkv, D)
            v = proj(x, "wv").reshape(B, T, Hkv, D)
        with scope("attn.rope"):
            q, k = apply_rotary_emb(q, k, *tables[sliding[j]])
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
            if config.attn_gate:
                with scope("attn.gate"):  # a scalar a head
                    gate = jax.nn.sigmoid(jnp.einsum(
                        "bth,oh->bto", x.astype(compute_dtype),
                        p["attn_gate"].astype(compute_dtype),
                        preferred_element_type=jnp.float32))
                    out = out * gate[..., None].astype(out.dtype)
        with scope("attn.proj"):
            out = proj(out.reshape(B, T, Hq * D).astype(compute_dtype), "wo")
        with scope("norm"):
            hidden = hidden + out
            y = rms_norm(hidden, p["mlp_norm"], eps).astype(compute_dtype)
        if "router" not in p:
            with scope("ffn.dense"):
                d = proj(jax.nn.silu(proj(y, "w_gate")) * proj(y, "w_up"),
                         "w_down")
            with scope("norm"):  # the add fuses with the next norm
                return hidden + d, c, None
        with scope("moe.router"):
            topv, topi = deepseek._router(config, y.reshape(B * T, -1), p)
            topv, topi = topv.reshape(B, T, -1), topi.reshape(B, T, -1)
        with scope("ffn"):
            d = llama._moe_dispatch(
                config, y, p, compute_dtype, topv, topi,
                layer=i if "w_up_e" in codes else None)
        if "w_up_s" in p:
            with scope("moe.shared"):  # ungated, at weight 1
                d = d + proj(jax.nn.silu(proj(y, "w_gate_s"))
                             * proj(y, "w_up_s"), "w_down_s")
        with scope("norm"):
            return hidden + d, c, topi

    first_routing = []
    for j in range(P):  # the first period: every layer its own weights
        h, cache, topi = layer(j, h, cache, params["first"][str(j)], {},
                               None, rank[j])
        if topi is not None:
            first_routing.append(topi)

    stacks = [_keep_codes_out(params["period"][str(j)]) for j in range(P)]

    def body(carry, xs):
        hidden, c, i = carry
        chosen = []
        for j, p in enumerate(xs):
            with scope("engine"):  # the loop's own counts
                idx = (i + 1) * per[sliding[j]] + rank[j]
            hidden, c, topi = layer(j, hidden, c, p, stacks[j][1], i, idx)
            chosen.append(topi)
        with scope("engine"):  # and the ids for the host
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
        if moe_routing:  # [n, P, B, T, k] -> the model's layer order
            extra = (jnp.concatenate(
                [t[None] for t in first_routing]
                + [routing.reshape((-1,) + routing.shape[2:])]),)
        if fresh:
            return (logits, None) + extra
        return (logits, kvwindow.advance(cache, T)) + extra
