"""Pipeline parallelism over a `pp` mesh axis.

TPU-native re-design of the reference's pipeline-parallel inference
(`transformers/pipeline_parallel.py:166-234` stage slicing with
Dummy layers, `:300-446` p2p send/recv token loop over oneCCL in
/root/reference): stages are shards of the **stacked layer axis** (the
same leading-L layout `lax.scan` iterates), microbatches flow stage to
stage via `ppermute` inside one jitted SPMD program — no process groups,
no explicit send/recv, and the whole GPipe schedule (fill, steady state,
drain: n_micro + n_stages - 1 ticks) compiles into a single XLA loop
with compute/ICI overlap.

Two entry points:

- `make_pipeline_forward`: microbatched GPipe forward for the cache-free
  scoring/training path (fill, steady state, drain ticks).
- `make_pipeline_step`: prefill/decode with **per-stage KV caches** —
  the cache's layer axis is sharded over `pp` exactly like the params,
  each stage's rows update at its tick, and the same step signature as
  the family forward lets `TpuModel.generate()` and the serving engine
  run unchanged over a (pp, tp) mesh (the reference's serving-grade
  `PPModelWorker`, pipeline_parallel.py:482-929, reaches this with
  explicit p2p + a Python scheduler; here it is one SPMD program).

On TPU slices tensor parallelism over ICI usually dominates PP; PP's
niche is multi-slice/DCN topologies and models bigger than one slice's
HBM.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.models.config import ModelConfig
from bigdl_tpu.quant import QTensor


def pipeline_param_specs(params: dict, axis: str = "pp") -> dict:
    """PartitionSpec tree: layer-stack leaves sharded on their leading L
    axis over `axis`; embed/head/final norm replicated (they run on the
    edge stages). QTensor nodes expand field-wise."""
    from bigdl_tpu.parallel.sharding import expand_specs_for_params

    is_node = lambda x: isinstance(x, (QTensor, jax.Array))
    specs = {
        k: jax.tree.map(
            lambda _: P(axis) if k == "layers" else P(), v, is_leaf=is_node
        )
        for k, v in params.items()
    }
    return expand_specs_for_params(specs, params)


def shard_for_pipeline(params: dict, mesh: Mesh, axis: str = "pp") -> dict:
    """Place a param tree with the layer stack split across pp stages."""
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        pipeline_param_specs(params, axis),
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.device_put(params, shardings)


def pp_param_specs(config: ModelConfig, base_specs: dict, axis: str = "pp") -> dict:
    """Compose PP with TP: take sharding.param_specs (tp dims) and put
    `axis` on the leading layer-stack dimension of every layers leaf."""

    def relayer(spec):
        if not isinstance(spec, P):
            return spec
        rest = tuple(spec)[1:] if len(spec) else ()
        return P(axis, *rest)

    out = dict(base_specs)
    out["layers"] = jax.tree.map(
        relayer, base_specs["layers"], is_leaf=lambda x: isinstance(x, P)
    )
    return out


def pp_cache_specs(cache, axis: str = "pp"):
    """PartitionSpec tree for a KVCache: per-layer arrays (k/v and their
    scales) sharded on the leading layer axis; positions replicated."""
    import dataclasses

    fields = {}
    for f in dataclasses.fields(cache):
        val = getattr(cache, f.name)
        if val is None:
            fields[f.name] = None
        elif f.name in ("k", "v", "k_scale", "v_scale"):
            fields[f.name] = P(axis)
        else:
            fields[f.name] = P()
    return type(cache)(**fields)


def _tree_where(pred, new, old):
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, old)


def make_pipeline_step(
    config: ModelConfig,
    forward_fn: Callable,
    mesh: Mesh,
    axis: str = "pp",
    compute_dtype=jnp.bfloat16,
):
    """Returns step(params, tokens, cache, mode=..., last_logits_only=...)
    -> (logits, cache): the family-forward signature, run as a pipeline
    over `axis` with per-stage KV caches.

    Params and cache carry their layer stacks sharded over `axis`
    (pp_param_specs / pp_cache_specs); any 'tp'/'dp' axes in the mesh
    stay automatic (GSPMD) — shard_map is manual over `axis` only. The
    token's hidden state flows stage to stage via ppermute across
    n_stages ticks; stage s commits its KV-cache rows only at tick s
    (a jnp.where select per tick — the price of one SPMD program).
    """
    n_stages = mesh.shape[axis]
    L = config.num_hidden_layers
    assert L % n_stages == 0, f"layers {L} not divisible by {n_stages} stages"
    L_local = L // n_stages
    perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

    from bigdl_tpu.models.llama import embed_tokens, lm_head_logits

    def step(params, tokens, cache, mode="decode", last_logits_only=False,
             collect_obs: int = 0):
        def stage_step(params, tokens, cache):
            s = jax.lax.axis_index(axis)
            h0 = embed_tokens(config, params, tokens, compute_dtype)
            B, T = tokens.shape
            # per-stage SnapKV observation queries, committed (like the
            # cache) only on the stage's active tick
            obs0 = jnp.zeros(
                (L_local, B, collect_obs, config.num_attention_heads,
                 config.head_dim_), compute_dtype,
            ) if collect_obs else None

            def tick(carry, t):
                recv, cache, out, obs = carry
                res = forward_fn(
                    config, params, recv, cache, mode=mode,
                    compute_dtype=compute_dtype, input_is_hidden=True,
                    return_hidden=True, layer_offset=s * L_local,
                    collect_obs=collect_obs,
                )
                if collect_obs:
                    h_out, cache_new, obs_new = res
                else:
                    (h_out, cache_new), obs_new = res, None
                active = s == t
                cache = _tree_where(active, cache_new, cache)
                if collect_obs:
                    obs = jnp.where(active, obs_new, obs)
                out = jnp.where(active & (s == n_stages - 1), h_out, out)
                recv = jax.lax.ppermute(h_out, axis, perm_fwd)
                return (recv, cache, out, obs), None

            (_, cache, out, obs), _ = jax.lax.scan(
                tick, (h0, cache, jnp.zeros_like(h0), obs0),
                jnp.arange(n_stages)
            )
            # psum: only the last stage holds the real hidden (V/H times
            # less ICI traffic than psumming logits). f32: XLA CPU's
            # AllReducePromotion pass check-fails cloning a bf16
            # all-reduce inside the generate while_loop (found round 3);
            # f32 sidesteps it at negligible cost for a [B,T,H] tensor.
            h_final = jax.lax.psum(
                jnp.where(s == n_stages - 1, out, 0.0).astype(jnp.float32),
                axis,
            ).astype(compute_dtype)
            if last_logits_only:
                h_final = h_final[:, -1:]
            logits = lm_head_logits(config, params, h_final, compute_dtype)
            if collect_obs:
                return logits, cache, obs
            return logits, cache

        from bigdl_tpu.parallel.sharding import param_specs

        pspecs = pp_param_specs(config, param_specs(config), axis)
        # drop non-pp axis names from the manual specs: shard_map is
        # manual over `axis` only; tp placement stays automatic
        def only_pp(spec):
            if not isinstance(spec, P):
                return spec
            return P(*(a if a == axis else None for a in tuple(spec)))

        pspecs = jax.tree.map(only_pp, pspecs, is_leaf=lambda x: isinstance(x, P))
        from bigdl_tpu.parallel.sharding import expand_specs_for_params

        pspecs = expand_specs_for_params(pspecs, params)
        out_specs = (P(), pp_cache_specs(cache, axis))
        if collect_obs:
            # obs stacks stage-local layer blocks -> global [L, B, W, Hq, D]
            out_specs = out_specs + (P(axis),)
        return jax.shard_map(
            stage_step,
            mesh=mesh,
            in_specs=(pspecs, P(), pp_cache_specs(cache, axis)),
            out_specs=out_specs,
            axis_names={axis},
            check_vma=False,
        )(params, tokens, cache)

    return step


def make_pipeline_forward(
    config: ModelConfig,
    forward_fn: Callable,  # family forward (models.llama.forward)
    mesh: Mesh,
    n_micro: int,
    axis: str = "pp",
    compute_dtype=jnp.bfloat16,
):
    """Returns fn(params, tokens [B,T], start [B]|None) -> logits
    [B,T,V] float32, with params layer-sharded over `axis`
    (shard_for_pipeline) and B divisible by n_micro.
    """
    n_stages = mesh.shape[axis]
    L = config.num_hidden_layers
    assert L % n_stages == 0, f"layers {L} not divisible by {n_stages} stages"
    L_local = L // n_stages
    perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

    from bigdl_tpu.models.llama import embed_tokens, lm_head_logits

    def stage_fn(params, tokens, start):
        s = jax.lax.axis_index(axis)
        B, T = tokens.shape
        Bm = B // n_micro
        toks_mb = tokens.reshape(n_micro, Bm, T)
        start_mb = start.reshape(n_micro, Bm)
        H = config.hidden_size

        n_ticks = n_micro + n_stages - 1
        outs0 = jnp.zeros((n_micro, Bm, T, H), compute_dtype)
        recv0 = jnp.zeros((Bm, T, H), compute_dtype)

        def tick(carry, t):
            recv, outs = carry
            m = t - s  # microbatch index at this stage this tick
            active = (m >= 0) & (m < n_micro)
            mi = jnp.clip(m, 0, n_micro - 1)
            toks_m = toks_mb[mi]
            start_m = start_mb[mi]
            # stage 0 embeds; later stages consume the ppermuted hidden
            h_in = jnp.where(
                s == 0, embed_tokens(config, params, toks_m, compute_dtype), recv
            )
            h_out, _ = forward_fn(
                config, params, h_in, None, compute_dtype=compute_dtype,
                start=start_m, input_is_hidden=True, return_hidden=True,
                layer_offset=s * L_local,
            )
            outs = jnp.where(
                active & (s == n_stages - 1),
                outs.at[mi].set(h_out),
                outs,
            )
            send = jax.lax.ppermute(h_out, axis, perm_fwd)
            return (send, outs), None

        (recv, outs), _ = jax.lax.scan(tick, (recv0, outs0), jnp.arange(n_ticks))
        # only the last stage holds real hiddens (zeros elsewhere): psum the
        # [B,T,H] hidden — V/H times less ICI traffic than psumming logits —
        # then run the replicated head locally on the identical summed value.
        h_final = jax.lax.psum(
            jnp.where(s == n_stages - 1, outs.reshape(B, T, H), 0.0), axis
        )
        return lm_head_logits(config, params, h_final, compute_dtype)

    def fn(params, tokens, start=None):
        if start is None:
            start = jnp.zeros((tokens.shape[0],), jnp.int32)
        sharded = jax.shard_map(
            stage_fn,
            mesh=mesh,
            in_specs=(pipeline_param_specs(params, axis), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        return sharded(params, tokens, start)

    return fn
