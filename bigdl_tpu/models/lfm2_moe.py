"""LFM2-MoE (`lfm2_moe`, e.g. LiquidAI/LFM2-24B-A2B): gated short-convolution
layers with a grouped-query attention layer every few, dense SwiGLU layers
first and then sigmoid-routed experts with a selection bias.

HF's modeling_lfm2_moe is the behavioural spec. Both residuals plain:

    h = embed(tokens)
    per layer:  h = h + operator(rmsnorm(h));  h = h + feed_forward(rmsnorm(h))
    logits = rmsnorm(h) @ embed^T          (`embedding_norm`; tied embeddings)

Convolution operator (`layer_types` "conv"), H the hidden size, K =
`conv_L_cache`: `[B | C | x] = in_proj(u)`, three of H in THAT order;
`g = B * x`; `c_t = sum_k w[k] * g_{t - (K - 1) + k}` a channel (depthwise,
causal, no bias, no activation); `out_proj(C * c)`. The state a sequence
carries is the last K - 1 values of `g` and nothing else
(`kvhybrid.tail_conv`). Attention operator: `num_attention_heads` query heads
on `num_key_value_heads` KV heads, no bias; an RMSNorm over each head of q
and of k BEFORE the rotation; rope over the whole head (rotate-half, no
scaling); causal. Feed-forward: the first `num_dense_layers` layers
`w2(silu(w1 u) * w3 u)`; the others `s = sigmoid(u W_g)` in float32, the
top-k of `s + expert_bias` chosen (the bias chooses and never weighs),
weights `s[chosen] / (sum + 1e-6) * routed_scaling_factor`, no shared
expert: `deepseek._router`'s sigmoid branch with one group.

Layout. `forward` walks the layers as RUNS of one kind and one feed-forward,
as `granitemoehybrid.forward` does and for its reason (a scan takes a whole
stack; nothing is sliced out of a larger one): `params["runs"]["00"]`, ...
Packed: `w_in`, `w_out`, q, k, v, o, the dense MLPs, the experts' three
stacks and the head's copy of the table. The convolution `[K, H]`, the
router and `e_bias` are float32 and stay as they are.

The cache is `kvhybrid.HybridCache` with NO recurrence state (`ssm` None):
a slot's state row is the convolution layers' tails, `conv [Lc, R, (K - 1) *
H]` float32, a row's inputs side by side on lanes. A head of 64 is half a
tile of lanes, so the attention layers' pool keeps its KV heads as LANE
PAIRS where that makes whole tiles (`ops/attention.lane_pairs`): `k, v [La,
n_pages, page, Hkv / 2, 128]`, a reshape of `[.., Hkv, 64]`; the queries are
padded to the pair's width (`pair_queries`) for the paged decode kernel and
for the prefill's flash kernel alike, which then run at `Hkv / 2` heads of
128, and the half of a context row that is not its head's is dropped.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu import kvhybrid, kvpaged
from bigdl_tpu.models import deepseek, granitemoehybrid, llama
from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import linear, rms_norm
from bigdl_tpu.ops.attention import lane_pairs, pair_queries, unpair_context
from bigdl_tpu.ops.linear import stacks_in

Params = dict[str, Any]

# the per-layer weights that go through `linear`, by kind of run
_MIXER_STACKS = {"conv": ("w_in", "w_out"),
                 "attention": ("wq", "wk", "wv", "wo")}
_MLP_STACKS = ("w_gate", "w_up", "w_down")
_QUANT_TARGETS = (_MIXER_STACKS["conv"] + _MIXER_STACKS["attention"]
                  + _MLP_STACKS + llama._EXPERT_STACKS)
ROUTER_EPS = 1e-6  # what HF adds to the chosen scores' sum


def layer_runs(config: ModelConfig) -> list[tuple[str, int, int, bool]]:
    """The layers as runs of one operator AND one feed-forward: (kind, index
    of the run's first layer AMONG ITS KIND, length, dense feed-forward)."""
    runs, seen = [], {"conv": 0, "attention": 0}
    for i, kind in enumerate(config.layer_types):
        dense = i < config.first_k_dense_replace or not config.is_moe
        if runs and runs[-1][0] == kind and runs[-1][3] == dense:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1, dense])
        seen[kind] += 1
    return [tuple(r) for r in runs]


def n_layers(config: ModelConfig, kind: str) -> int:
    return sum(k == kind for k in config.layer_types)


def kv_layout(config: ModelConfig) -> tuple[int, int]:
    """(heads, head size) of the attention layers' pool AS STORED: lane
    pairs where the published heads make them."""
    Hkv, D = config.num_key_value_heads, config.head_dim_
    return (Hkv // 2, 2 * D) if lane_pairs(Hkv, D) else (Hkv, D)


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                scale: float = 0.02) -> Params:
    """Random init (tests/benchmarks run without checkpoints)."""
    hid, V, D = config.hidden_size, config.vocab_size, config.head_dim_
    F, K = config.intermediate_size, config.conv_l_cache
    E, I = config.num_experts, config.moe_intermediate_size
    runs = layer_runs(config)
    keys = iter(jax.random.split(key, 16 * (len(runs) + 1)))

    def w(shape, std=scale, dt=dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dt)

    def run(kind, n, dense):
        p = {"attn_norm": jnp.ones((n, hid), dtype),
             "mlp_norm": jnp.ones((n, hid), dtype)}
        if kind == "conv":
            p.update(w_in=w((n, 3 * hid, hid)), w_out=w((n, hid, hid)),
                     conv_w=w((n, K, hid), std=K ** -0.5, dt=jnp.float32))
        else:
            QD, KD = config.q_dim, config.kv_dim
            p.update(wq=w((n, QD, hid)), wk=w((n, KD, hid)),
                     wv=w((n, KD, hid)), wo=w((n, hid, QD)),
                     q_norm=jnp.ones((n, D), dtype),
                     k_norm=jnp.ones((n, D), dtype))
        if dense:
            p.update(w_gate=w((n, F, hid)), w_up=w((n, F, hid)),
                     w_down=w((n, hid, F)))
        else:
            p.update(router=w((n, E, hid), dt=jnp.float32),
                     e_bias=jnp.zeros((n, E), jnp.float32),
                     w_gate_e=w((n, E, I, hid)), w_up_e=w((n, E, I, hid)),
                     w_down_e=w((n, E, hid, I)))
        return p

    params: Params = {
        "embed": w((V, hid)),
        "runs": {f"{r:02d}": run(kind, n, dense)
                 for r, (kind, _, n, dense) in enumerate(runs)},
        "final_norm": jnp.ones((hid,), dtype)}
    if not config.tie_word_embeddings:
        params["lm_head"] = w((V, hid))
    return params


def quantize_params(params: Params, qtype: str,
                    lm_head_qtype: Optional[str] = None) -> Params:
    """Pack the projections, the dense MLPs and the experts; the
    convolution, the router, `e_bias` and the norms stay as they are. With
    tied embeddings the head becomes a PACKED COPY of the table
    (`lm_head`)."""
    return granitemoehybrid.quantize_params(params, qtype, lm_head_qtype,
                                            targets=_QUANT_TARGETS)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_paged_cache(config: ModelConfig, n_pages: int, page_size: int,
                     batch: int, max_pages_per_row: int
                     ) -> kvhybrid.HybridCache:
    """The family's PAGED cache for `InferenceEngine(paged=True)`: pages of
    keys and values for the attention layers (lane pairs, `kv_layout`) and
    one state row a slot for the convolution layers, which is their tails
    and nothing else (`kvhybrid`)."""
    return kvhybrid.init_hybrid(
        n_layers(config, "attention"), n_layers(config, "conv"), n_pages,
        page_size, *kv_layout(config), batch, max_pages_per_row,
        config.hidden_size, config.conv_l_cache, None, conv_rows=1)


PAGED_CACHE_KIND = kvhybrid.KIND


def init_cache(config: ModelConfig, batch: int, cache_len: int = 0,
               quantize_kv: bool = False) -> kvhybrid.HybridCache:
    """`generate_tokens`' family hook: every row's pages in order."""
    return granitemoehybrid.init_cache(config, batch, cache_len, quantize_kv,
                                       paged=init_paged_cache)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def gate_parts(bcx: jax.Array) -> tuple:
    """`in_proj`'s output [.., 3 H] as (B, C, x), float32: HF's order."""
    B, C, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return B, C, x


def _keep_codes_out(group: Params, kind: str) -> tuple[Params, dict]:
    """`granitemoehybrid._keep_codes_out` for this family's groups."""
    from bigdl_tpu.ops.linear import grouped_route, stacks_out

    names = [n for n in _MIXER_STACKS[kind] + _MLP_STACKS
             if n in group and grouped_route(group[n]) is None]
    if "w_up_e" in group and llama.moe_grouped_why_not(group, False) is None:
        names += list(llama._EXPERT_STACKS)
    return stacks_out(group, names)


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[kvhybrid.HybridCache],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
    moe_routing: bool = False,  # static: also return every SPARSE layer's
    # top-k expert ids [L - num_dense_layers, B, T, k] int32, in the model's
    # layer order (the dense layers route nothing)
):
    """Returns (logits [B, T, V] float32, the cache with `pos` advanced),
    and the routing third when asked. `cache` None runs a whole sequence
    from nothing and keeps nothing."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas
    from bigdl_tpu.ops.rope import (apply_rotary_emb, default_inv_freq,
                                    rope_cos_sin)

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    Hq, Hkv, D = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim_)
    eps, scale = config.rms_norm_eps, D ** -0.5
    decode = mode == "decode" and T == 1
    pairs = kv_layout(config) != (Hkv, D)

    fresh = cache is None
    if fresh:
        with scope("engine"):
            cache = init_cache(config, B, T)

    with scope("engine"):
        slots = cache.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    detail = (f"mode={mode} B{B} T{T} {Hq} heads on {Hkv} of {D}"
              + (f" as {Hkv // 2} lane pairs" if pairs else ""))
    use_kernel = decode and use_pallas()
    use_flash = T > 1 and B == 1 and use_pallas()
    if use_kernel:
        routes.note("attention", "pallas:paged", detail)
        with scope("attn"):
            row_live = kvpaged.live_rows(cache)
    elif use_flash:
        routes.note("attention", "pallas:flash", detail)
    else:
        routes.note("attention", "xla",
                    f"{detail} ({why_not_pallas() or 'B > 1'})")
        with scope("attn"):  # the mask, once for every layer
            sj = jnp.arange(cache.max_len)
            mask = ((sj[None, None, :] <= slots[..., None])
                    & (sj[None, None, :] >= cache.start[:, None, None]))
            mask = mask[:, None, None]  # [B, 1, 1, T, S]

    with scope("attn.rope"):
        cos, sin = rope_cos_sin(cache.kv.next_positions(T),
                                default_inv_freq(D, config.rope_theta))
    with scope("engine"):
        h = llama.embed_tokens(config, params, tokens, compute_dtype)

    def attn_mixer(x, p, c, idx, proj):
        """GQA over layer `idx`'s pages: q/k norm a head, then the rope."""
        with scope("attn.proj"):
            q = proj(x, p, "wq").reshape(B, T, Hq, D)
            k = proj(x, p, "wk").reshape(B, T, Hkv, D)
            v = proj(x, p, "wv").reshape(B, T, Hkv, D)
        with scope("attn.rope"):
            q = rms_norm(q, p["q_norm"], eps)
            k = rms_norm(k, p["k_norm"], eps)
            q, k = apply_rotary_emb(q, k, cos, sin)
        # the pool's own layout: a reshape in row-major order
        stored = (B, T) + c.k.shape[3:]
        kv = kvpaged.update_layer(c.kv, idx, k.reshape(stored),
                                  v.reshape(stored))
        c = dataclasses.replace(c, k=kv.k, v=kv.v)
        if use_kernel or use_flash:
            if pairs:
                with scope("pair_attn"):
                    q = pair_queries(q, Hkv)
            if use_kernel:
                from bigdl_tpu.ops.pallas import paged_decode_attention

                out = paged_decode_attention(
                    q[:, 0], c.k, c.v, c.block_tables, idx, c.pos, c.start,
                    scale=scale, live=row_live)[:, None]
            else:
                from bigdl_tpu.ops.pallas import flash_attention

                kf, vf = kvpaged.read_layer(kv, idx, compute_dtype)
                out = flash_attention(q, kf, vf, start=c.start,
                                      q_offset=c.pos[0], scale=scale)
            if pairs:
                with scope("pair_attn"):
                    out = unpair_context(out, Hkv)
        else:  # plain attention on the published heads
            kf, vf = kvpaged.read_layer(kv, idx, compute_dtype)
            heads = kf.shape[:2] + (Hkv, D)
            out = attention(q, kf.reshape(heads), vf.reshape(heads),
                            mask=mask, scale=scale)
        with scope("attn.proj"):
            return proj(out.reshape(B, T, Hq * D).astype(compute_dtype), p,
                        "wo"), c

    def conv_mixer(x, p, c, idx, proj):
        bcx = proj(x, p, "w_in")  # [B, T, 3 H]
        with scope("short_conv"):
            gate_in, gate_out, xs = gate_parts(bcx)
            y, c = kvhybrid.tail_conv(c, idx, gate_in * xs, p["conv_w"],
                                      decode=decode)
            y = (gate_out * y).astype(compute_dtype)
        return proj(y, p, "w_out"), c

    def layer(kind, hidden, c, p, codes, idx, at):
        """One decoder layer: number `idx` of its run (which finds it in
        the unsliced codes) and number `at` of its kind (in the cache)."""
        p = stacks_in(p, codes)

        def proj(x, p, name):
            return linear(x, p[name], None, compute_dtype,
                          layer=idx if name in codes else None)

        with scope("norm"):
            x = rms_norm(hidden, p["attn_norm"], eps)
        with scope("attn" if kind == "attention" else "mamba2"):
            out, c = (attn_mixer if kind == "attention" else conv_mixer)(
                x, p, c, at, proj)
        with scope("norm"):
            hidden = hidden + out
            x = rms_norm(hidden, p["mlp_norm"], eps).astype(compute_dtype)
        if "router" not in p:
            with scope("ffn.dense"):
                d = proj(jax.nn.silu(proj(x, p, "w_gate"))
                         * proj(x, p, "w_up"), p, "w_down")
            with scope("norm"):  # the add fuses with the next norm
                return hidden + d, c, None
        with scope("moe.router"):
            topv, topi = deepseek._router(config, x.reshape(B * T, -1), p,
                                          norm_eps=ROUTER_EPS)
            topv, topi = topv.reshape(B, T, -1), topi.reshape(B, T, -1)
        with scope("ffn"):
            d = llama._moe_dispatch(
                config, x, p, compute_dtype, topv, topi,
                layer=idx if "w_up_e" in codes else None)
        with scope("norm"):
            return hidden + d, c, topi

    routing = []
    c = cache
    with scope("engine"):
        zero = jnp.zeros((), jnp.int32)
    for (kind, first, n, dense), r in zip(layer_runs(config),
                                          sorted(params["runs"])):
        sliced, codes = _keep_codes_out(params["runs"][r], kind)
        if n == 1:
            with scope("engine"):  # the one layer out of its stack
                p1, at = jax.tree.map(lambda a: a[0], sliced), zero + first
            h, c, topi = layer(kind, h, c, p1, codes, zero, at)
            if moe_routing and not dense:
                with scope("engine"):
                    routing.append(topi[None])
            continue

        def body(carry, p, kind=kind, codes=codes, first=first):
            hidden, c, idx = carry
            with scope("engine"):  # the loop's own counts
                at = idx + first
            hidden, c, topi = layer(kind, hidden, c, p, codes, idx, at)
            with scope("engine"):
                return (hidden, c, idx + 1), topi if moe_routing else None

        (h, c, _), topi = jax.lax.scan(body, (h, c, zero), sliced)
        if moe_routing and not dense:
            routing.append(topi)

    with scope("lm_head"):
        if last_logits_only:
            h = h[:, -1:]
        logits = llama.lm_head_logits(config, params, h, compute_dtype)
    with scope("engine"):
        extra = (jnp.concatenate(routing, axis=0),) if moe_routing else ()
        if fresh:
            return (logits, None) + extra
        return (logits, kvhybrid.advance(c, T)) + extra
