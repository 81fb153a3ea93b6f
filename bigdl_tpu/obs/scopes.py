"""The names of the spans INSIDE the device's programs.

A step program (`engine_decode`, `engine_paged_prefill`,
`engine_first_token`, `generate_tokens`, the block kind's pass) puts every
operation it traces under one of the names below, with `scope(name)`:
`jax.named_scope` after a check that the name is one of them. The name
reaches the compiled program as the operation's metadata and a profile as
its `tf_op`; it adds no equation, so a program is the same program with or
without it. Scopes nest, and an operation belongs to the INNERMOST one
(`moe.router` inside `ffn` is the router's). docs/observability.md section 4
has the table of what each holds; `bench/reduce/scopes.py` reduces a
profile's device time by them.

The one thing a name costs is a context manager where a program is traced.
"""

from __future__ import annotations

import jax

#: every name a step program may use: flat dotted strings, at most 20
VOCABULARY = (
    # the step program around the layers: the token gather (the embedding),
    # positions, what the engine does before `forward` (a row's view of the
    # pool, per-row adapters, the key split) and after the sampler (the
    # chosen token's log-probability, `seen`, what is packed for the host,
    # `advance`, the pages written back)
    "engine",
    # layer norms, the final norm, the residual adds that fuse with them
    "norm",
    # the mixer: its projections; q/k norm, rotary tables and rotation,
    # `logn`; the rest of attention (the write of the new K/V, latent or
    # state row, any view the kernel is handed, the kernel, what XLA does
    # between them); a head's output gate
    "attn.proj",
    "attn.rope",
    "attn",
    "attn.gate",
    "mamba2",
    "power_retention_prefill",
    "mamba2_prefill",
    # the feed-forward: a dense MLP (`ffn`; `ffn.dense` where the other
    # layers are sparse), and a sparse layer's router, shared expert, and
    # the three parts of its routed experts
    "ffn",
    "ffn.dense",
    "moe.router",
    "moe.shared",
    "moe.dispatch",
    "moe.experts",
    "moe.combine",
    # the head and what chooses a token from it
    "lm_head",
    "sample",
    "block.reveal",
    "block.store",
)

#: names that stand INSIDE a scope of the vocabulary and split it further
#: by hand (`scripts/trace_ops_by_program.py`): the reducer and the metrics
#: know the vocabulary alone, and an operation under one of these belongs to
#: the vocabulary's scope around it (`bench/reduce/scopes.scope_of` takes the
#: innermost name it knows). A block-sparse layer's selection, and the two
#: forms of a lightning (decayed linear attention) layer (kvsparse.py), and
#: of a Mamba-1 layer's selective scan (kvhybrid.mix1); of a gated
#: short-convolution layer everything but its two projections (the gate, the
#: convolution, the tail's update), and the padding and slicing around
#: attention over lane pairs (models/lfm2_moe.py); the two forms of a Kimi
#: delta attention layer's delta rule (kvhybrid.kda_mix), whose three
#: convolutions stand under `short_conv`.
DETAIL = ("sparse_select", "lightning_prefill", "lightning_decode",
          "mamba1_prefill", "mamba1_decode", "short_conv", "pair_attn",
          "kda_prefill", "kda_decode")

_NAMES = frozenset(VOCABULARY + DETAIL)


def scope(name: str):
    """`jax.named_scope(name)` for a name of `VOCABULARY` (or of `DETAIL`,
    inside one); any other name raises where the program is traced."""
    if name not in _NAMES:
        raise ValueError(
            f"{name!r} is not a scope of bigdl_tpu.obs.scopes.VOCABULARY; "
            f"have {sorted(VOCABULARY)} and, inside them, {sorted(DETAIL)}")
    return jax.named_scope(name)
