"""Solar-Open2 (`solar_open2`, e.g. upstage/Solar-Open2-250B): Kimi delta
attention (KDA) layers with a gated grouped-query attention layer WITHOUT
positions every few, every layer followed by sigmoid-routed experts with a
selection bias and one ungated shared expert.

The catalog row of the model (its `config.json` keys and their summary) is
the behavioural spec; every reading it leaves open stands under `assumed` in
bench/configs/solar-open2-250b-int4.json. Both residuals plain, RMSNorm:

    h = embed(tokens)
    per layer:  h = h + mixer(rmsnorm(h));  h = h + moe(rmsnorm(h))
    logits = lm_head(rmsnorm(h))                     (untied)

KDA mixer (`layer_types` "kda"; H heads of D = 128 keys and values, x the
layer's normed input):

    q^, k^, v^ = Wq x, Wk x, Wv x                    each [T, H * D]
    q', k', v = silu(conv_K(.))     three depthwise causal convolutions, no
                                    bias: ONE over the three side by side
    q = q' / max(|q'|, 1e-6) / sqrt(D);  k = k' / max(|k'|, 1e-6)   a head
    g = -exp(A_log[h]) * softplus(W_fb (W_fa x) + dt_bias)   [T, H, D] <= 0
    beta = 2 * sigmoid(W_beta x)                             [T, H]
    the delta rule of `kvhybrid.kda_mix` (float32 state [D, D] a head)
    y = rmsnorm_head(o; o_norm) * sigmoid(W_gb (W_ga x) + g_bias)
    out = Wo y

GQA mixer ("attention"): q on `num_attention_heads` heads, k, v on
`num_key_value_heads`, no rope, no bias, causal softmax over the whole
context, `y = attn * sigmoid(Wg x)` lane for lane, `Wo y`.

Experts: `deepseek._router`'s sigmoid branch with one group and a selection
bias over the router's WHOLE width, the routed part by `llama._moe_dispatch`
(which keeps one rank's share where the configuration holds one:
`ModelConfig.expert_share`) and the shared SwiGLU through `linear`.

Layout, as `lfm2_moe.py`: `forward` walks the layers as RUNS of one kind,
`params["runs"]["00"]`, ... Packed: q, k, v, the GQA gate, o, the experts'
three stacks, the shared expert and the head. The low-rank pairs and
`w_beta` are bfloat16 and stay as they are, the convolution `[K, 3 * H * D]`,
`A_log`, `dt_bias`, `g_bias`, the router and `e_bias` float32.

The cache is `kvhybrid.HybridCache`: pages for the GQA layers and one state
row a slot for the KDA layers (the three convolutions' tails in one piece,
and `ssm [Lk, R, H * D, D]`).
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
from bigdl_tpu.ops.linear import stacks_in

Params = dict[str, Any]

KDA, ATTENTION = "kda", "attention"
LOW_RANK = 128  # of the decay's and the output gate's pairs: the head size
# the per-layer weights that go through `linear`, by kind of run
_MIXER_STACKS = {KDA: ("wq", "wk", "wv", "wo"),
                 ATTENTION: ("wq", "wk", "wv", "wg", "wo")}
_SHARED_STACKS = ("w_gate_s", "w_up_s", "w_down_s")
_QUANT_TARGETS = (_MIXER_STACKS[ATTENTION] + _SHARED_STACKS
                  + llama._EXPERT_STACKS)
PAGED_CACHE_KIND = kvhybrid.KIND


def layer_runs(config: ModelConfig) -> list[tuple[str, int, int]]:
    """`layer_types` as runs: (kind, index of the run's first layer AMONG
    ITS KIND, length)."""
    runs, seen = [], {KDA: 0, ATTENTION: 0}
    for kind in config.layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in runs]


def n_layers(config: ModelConfig, kind: str) -> int:
    return sum(k == kind for k in config.layer_types)


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16,
                scale: float = 0.02) -> Params:
    """Random init (tests/benchmarks run without checkpoints); `A_log` and
    `dt_bias` as Kimi Linear draws them (a decay of 1 to 16 times a step of
    0.001 to 0.1: a state that outlives a chunk)."""
    hid, V = config.hidden_size, config.vocab_size
    H, D, K = config.kda_heads, config.kda_head_dim, config.conv_l_cache
    E, Er, I = (config.num_experts, config.router_width,
                config.moe_intermediate_size)
    Is = I * (config.n_shared_experts or 0)
    QD, KD, HD = config.q_dim, config.kv_dim, H * D
    runs = layer_runs(config)
    keys = iter(jax.random.split(key, 32 * (len(runs) + 1)))
    f32 = jnp.float32

    def w(shape, std=scale, dt=dtype):
        return (jax.random.normal(next(keys), shape, f32) * std).astype(dt)

    def run(kind, n):
        p = {"attn_norm": jnp.ones((n, hid), dtype),
             "mlp_norm": jnp.ones((n, hid), dtype),
             "router": w((n, Er, hid), dt=f32),
             "e_bias": jnp.zeros((n, Er), f32),
             "w_gate_e": w((n, E, I, hid)), "w_up_e": w((n, E, I, hid)),
             "w_down_e": w((n, E, hid, I))}
        if Is:
            p.update(w_gate_s=w((n, Is, hid)), w_up_s=w((n, Is, hid)),
                     w_down_s=w((n, hid, Is)))
        if kind == ATTENTION:
            p.update(wq=w((n, QD, hid)), wk=w((n, KD, hid)),
                     wv=w((n, KD, hid)), wg=w((n, QD, hid)),
                     wo=w((n, hid, QD)))
            return p
        dt = jnp.exp(jax.random.uniform(
            next(keys), (n, HD), f32, jnp.log(1e-3), jnp.log(0.1)))
        p.update(wq=w((n, HD, hid)), wk=w((n, HD, hid)), wv=w((n, HD, hid)),
                 wo=w((n, hid, HD)),
                 conv_w=w((n, K, 3 * HD), std=K ** -0.5, dt=f32),
                 f_a=w((n, LOW_RANK, hid)), f_b=w((n, HD, LOW_RANK)),
                 g_a=w((n, LOW_RANK, hid)), g_b=w((n, HD, LOW_RANK)),
                 g_bias=jnp.zeros((n, HD), f32), w_beta=w((n, H, hid)),
                 A_log=jnp.log(jax.random.uniform(next(keys), (n, H), f32,
                                                  1.0, 16.0)),
                 dt_bias=dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
                 o_norm=jnp.ones((n, D), dtype))
        return p

    return {"embed": w((V, hid)),
            "runs": {f"{r:02d}": run(kind, n)
                     for r, (kind, _, n) in enumerate(runs)},
            "final_norm": jnp.ones((hid,), dtype),
            "lm_head": w((V, hid))}


def quantize_params(params: Params, qtype: str,
                    lm_head_qtype: Optional[str] = None) -> Params:
    """Pack the mixers' projections, the experts, the shared expert and the
    head; the low-rank pairs, `w_beta`, the convolution, `A_log`, `dt_bias`,
    `g_bias`, the router, `e_bias`, the norms and the embedding stay as
    they are."""
    return granitemoehybrid.quantize_params(params, qtype, lm_head_qtype,
                                            targets=_QUANT_TARGETS)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_paged_cache(config: ModelConfig, n_pages: int, page_size: int,
                     batch: int, max_pages_per_row: int
                     ) -> kvhybrid.HybridCache:
    """The family's PAGED cache for `InferenceEngine(paged=True)`: pages of
    keys and values for the GQA layers and one state row a slot for the KDA
    layers: the three convolutions' tails in one piece and a `[D, D]` state
    a head (`kvhybrid`)."""
    H, D = config.kda_heads, config.kda_head_dim
    return kvhybrid.init_hybrid(
        n_layers(config, ATTENTION), n_layers(config, KDA), n_pages,
        page_size, config.num_key_value_heads, config.head_dim_, batch,
        max_pages_per_row, 3 * H * D, config.conv_l_cache, (H * D, D),
        counts=("state_chunks", kvhybrid.KDA_CHUNK), conv_rows=1)


def init_cache(config: ModelConfig, batch: int, cache_len: int = 0,
               quantize_kv: bool = False) -> kvhybrid.HybridCache:
    """`generate_tokens`' family hook: every row's pages in order."""
    return granitemoehybrid.init_cache(config, batch, cache_len, quantize_kv,
                                       paged=init_paged_cache)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _keep_codes_out(group: Params, kind: str) -> tuple[Params, dict]:
    """`granitemoehybrid._keep_codes_out` for this family's groups."""
    from bigdl_tpu.ops.linear import grouped_route, stacks_out

    names = [n for n in _MIXER_STACKS[kind] + _SHARED_STACKS
             if n in group and grouped_route(group[n]) is None]
    if llama.moe_grouped_why_not(group, False) is None:
        names += list(llama._EXPERT_STACKS)
    return stacks_out(group, names)


def _low_rank(x, a, b):
    """`b (a x)`: bfloat16 operands as `linear`'s, float32 sums."""
    f32 = jnp.float32
    mid = jnp.einsum("bti,ri->btr", x.astype(a.dtype), a,
                     preferred_element_type=f32)
    return jnp.einsum("btr,or->bto", mid.astype(b.dtype), b,
                      preferred_element_type=f32)


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, T] int32
    cache: Optional[kvhybrid.HybridCache],
    mode: str = "prefill",
    compute_dtype=jnp.bfloat16,
    last_logits_only: bool = False,
    logits_at=None,  # traced position: the head on that one position only
    # (the engine's prefill wants the last TOKEN's logits: [T, V] at T =
    # 4096 and 196608 rows would be 3.2 GB)
    moe_routing: bool = False,  # static: also return every layer's top-k
    # expert ids [L, B, T, k] int32 over the ROUTER's width, in the model's
    # layer order
):
    """Returns (logits [B, T, V] float32, the cache with `pos` advanced),
    and the routing third when asked. `cache` None runs a whole sequence
    from nothing and keeps nothing."""
    from bigdl_tpu.ops import routes
    from bigdl_tpu.ops.attention import attention
    from bigdl_tpu.ops.pallas import use_pallas, why_not_pallas

    assert mode in ("prefill", "decode")
    B, T = tokens.shape
    Hq, Hkv, D = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim_)
    KH, KD = config.kda_heads, config.kda_head_dim
    eps, scale = config.rms_norm_eps, D ** -0.5
    decode = mode == "decode" and T == 1
    f32 = jnp.float32

    fresh = cache is None
    if fresh:
        with scope("engine"):
            cache = init_cache(config, B, T)

    with scope("engine"):
        slots = cache.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    detail = f"mode={mode} B{B} T{T} {Hq} heads on {Hkv} of {D} nope gated"
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
    with scope("engine"):
        h = llama.embed_tokens(config, params, tokens, compute_dtype)

    def attn_mixer(x, p, c, idx, proj):
        """Gated GQA over layer `idx`'s pages, no positions."""
        with scope("attn.proj"):
            q = proj(x, p, "wq").reshape(B, T, Hq, D)
            k = proj(x, p, "wk").reshape(B, T, Hkv, D)
            v = proj(x, p, "wv").reshape(B, T, Hkv, D)
            gate = proj(x, p, "wg")
        kv = kvpaged.update_layer(c.kv, idx, k, v)
        c = dataclasses.replace(c, k=kv.k, v=kv.v)
        if use_kernel:
            from bigdl_tpu.ops.pallas import paged_decode_attention

            out = paged_decode_attention(
                q[:, 0], c.k, c.v, c.block_tables, idx, c.pos, c.start,
                scale=scale, live=row_live)[:, None]
        else:
            kf, vf = kvpaged.read_layer(kv, idx, compute_dtype)
            if use_flash:
                from bigdl_tpu.ops.pallas import flash_attention

                out = flash_attention(q, kf, vf, start=c.start,
                                      q_offset=c.pos[0], scale=scale)
            else:
                out = attention(q, kf, vf, mask=mask, scale=scale)
        with scope("attn.gate"):
            out = (out.reshape(B, T, Hq * D).astype(f32)
                   * jax.nn.sigmoid(gate.astype(f32)))
        with scope("attn.proj"):
            return proj(out.astype(compute_dtype), p, "wo"), c

    def kda_mixer(x, p, c, idx, proj):
        with scope("attn.proj"):
            qkv = jnp.concatenate(
                [proj(x, p, n).astype(f32) for n in ("wq", "wk", "wv")],
                axis=-1)
            g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
                _low_rank(x, p["f_a"], p["f_b"]) + p["dt_bias"]
            ).reshape(B, T, KH, KD)
            beta = 2.0 * jax.nn.sigmoid(jnp.einsum(
                "bti,hi->bth", x.astype(p["w_beta"].dtype), p["w_beta"],
                preferred_element_type=f32))
        o, c = kvhybrid.kda_mix(c, idx, qkv, g, beta, p["conv_w"],
                                n_heads=KH, d_head=KD, decode=decode)
        with scope("attn.gate"):
            o = rms_norm(o, p["o_norm"], eps).astype(f32)
            o = o.reshape(B, T, KH * KD) * jax.nn.sigmoid(
                _low_rank(x, p["g_a"], p["g_b"]) + p["g_bias"])
        with scope("attn.proj"):
            return proj(o.astype(compute_dtype), p, "wo"), c

    def layer(kind, hidden, c, p, codes, idx, at):
        """One decoder layer: number `idx` of its run (which finds it in
        the unsliced codes) and number `at` of its kind (in the cache)."""
        p = stacks_in(p, codes)

        def proj(x, p, name):
            return linear(x, p[name], None, compute_dtype,
                          layer=idx if name in codes else None)

        with scope("norm"):
            x = rms_norm(hidden, p["attn_norm"], eps)
        with scope("attn"):
            out, c = (attn_mixer if kind == ATTENTION else kda_mixer)(
                x, p, c, at, proj)
        with scope("norm"):
            hidden = hidden + out
            x = rms_norm(hidden, p["mlp_norm"], eps).astype(compute_dtype)
        with scope("moe.router"):
            topv, topi = deepseek._router(config, x.reshape(B * T, -1), p)
            topv, topi = topv.reshape(B, T, -1), topi.reshape(B, T, -1)
        with scope("ffn"):
            d = llama._moe_dispatch(
                config, x, p, compute_dtype, topv, topi,
                layer=idx if "w_up_e" in codes else None)
            if "w_up_s" in p:
                with scope("moe.shared"):
                    d = d + proj(
                        jax.nn.silu(proj(x, p, "w_gate_s"))
                        * proj(x, p, "w_up_s"), p, "w_down_s")
        with scope("norm"):
            return hidden + d, c, topi

    routing = []
    c = cache
    with scope("engine"):
        zero = jnp.zeros((), jnp.int32)
    for (kind, first, n), r in zip(layer_runs(config),
                                   sorted(params["runs"])):
        sliced, codes = _keep_codes_out(params["runs"][r], kind)
        if n == 1:
            with scope("engine"):  # the one layer out of its stack
                p1, at = jax.tree.map(lambda a: a[0], sliced), zero + first
            h, c, topi = layer(kind, h, c, p1, codes, zero, at)
            if moe_routing:
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
        if moe_routing:
            routing.append(topi)

    with scope("lm_head"):
        if logits_at is not None:
            h = jax.lax.dynamic_slice_in_dim(h, logits_at, 1, axis=1)
        elif last_logits_only:
            h = h[:, -1:]
        logits = llama.lm_head_logits(config, params, h, compute_dtype)
    with scope("engine"):
        extra = (jnp.concatenate(routing, axis=0),) if moe_routing else ()
        if fresh:
            return (logits, None) + extra
        return (logits, kvhybrid.advance(c, T)) + extra
