"""SDAR-MoE (JetLM, SDAR-30B-A3B-Chat): Qwen3-MoE's network, generated by
DIFFUSION OVER BLOCKS.

Nothing of the network is written here: `models/llama.forward` runs it (the
per-head RMSNorm on q and k, 128 softmax-routed experts of 768, top-8
renormalised). What differs is how it generates, and `config.block_length`
says so to whoever runs the forward:

- the attention mask is causal BY BLOCK (key j is visible to query i iff
  j // b <= i // b): `forward(mode="prefill")` under that mask stores a
  prompt's whole blocks, and `forward(mode="block")` runs one block's `b`
  positions at the row's own offset against everything stored;
- the logits at position i score the token AT i (a masked position
  predicts itself; there is no shift by one);
- a block starts as `mask_token_id` and is revealed over a few passes, the
  most confident first. The loop, its device half and its host half, is
  `serving/blocks.py`, behind `InferenceEngine(paged=True)`; the cache is
  the plain kind of KV pages (`kvpaged.KV_PAGES`, prefixes unshared).

`TpuModel.generate` decodes one token a step from the logits of the step
before and so cannot run this family: it refuses by name (`api.py`).
"""

from __future__ import annotations

from bigdl_tpu.models import llama

init_params = llama.init_params
quantize_params = llama.quantize_params
forward = llama.forward
merge_fused_params = llama.merge_fused_params
unmerge_fused_params = llama.unmerge_fused_params
