"""Generation by diffusion over blocks, behind `InferenceEngine(paged=True)`.

A model with `config.block_length = b > 0` (models/sdar.py) does not yield
one token a row and step. Its unit of work is a BLOCK of `b` positions:
every position of a block starts as `mask_token_id`, a PASS is one forward
over the block's current ids against everything stored before it (both
directions inside the block, `llama.forward(mode="block")`), and each pass
reveals some of the still-masked positions, the most confident first:

    x0 = argmax of a masked position's logits (a sampled id at
         temperature > 0), c = softmax(logits)[x0]
    "sequential"              the leftmost masked position
    "low_confidence_static"   the m_s masked positions of largest c,
                              m_s = b // steps (+ 1 in the first b % steps
                              passes of the block)
    "low_confidence_dynamic"  every masked position with c > threshold,
                              and the m_s largest where fewer pass it

A revealed position keeps its id. When a pass FINDS no mask left, it is the
block's STORE pass: its forward writes the block's K and V from the final
ids (every earlier pass wrote them too, from ids that still held masks, to
the same slots), reveals nothing, and the row moves on to the next block,
all masks again. The store is a pass of its own here, as in the source's
loop; folding it into the next block's first pass (2b positions a row) would
change no number and save a pass in `steps + 1`: ROADMAP R9.

The pass is the engine's decode step: the program is `engine_decode`, the
span `decode_step`, and the one step in flight of plain decode stays,
because the reveal runs ON THE DEVICE: a row's ids, which of them are
revealed and its pass number are arrays the program carries (`BlockState`),
so pass N + 1 is enqueued before the host has read pass N. What the host
reads of a pass (`BlockScheduler.read`) is, a row: the ids the pass ran on,
the candidate ids and their log-confidences, which positions were masked
and which of them it revealed, whether it stored, and the experts it chose. Tokens leave in position order
(one revealed ahead of an earlier position waits for it), a request ends
INSIDE a block once its `max_new_tokens` have left, and what the last block
holds past them is dropped.

An admission's prefill stores the prompt's WHOLE blocks under the mask
causal by block and yields no token; the `P % b` tokens left over open the
first block beside its masks (`arm`). No `engine_first_token` runs.

Steps, strategy and threshold are the MODEL CONFIG's (`denoising_steps`,
`remasking_strategy`, `confidence_threshold`): constants of an engine,
static in its pass program. An operator who wants other passes a block
serves another config.

The engine owns the two programs (`engine._decode` is `pass_impl` jitted as
`engine_decode`, `engine._arm_block` is `arm_impl`) and calls them; this
module holds what they compute, the state between passes and the host half.

Not served yet, each refused by name at construction (`CACHE_KIND.refuses`,
`BlockScheduler.__init__`): speculative rounds, fp8 pages, adapters, top-k
alternatives, chunked prefill (a chunk would have to end on a block), and,
by `share_prefixes = False`, prefix hits (a hit would have to be cut to
whole blocks). Preemption is off for this kind (the engine's constructor
switches it off as for a family cache: a parked row would have to keep a
block half revealed): a pool too small ends the row with "length", and
`preempt()` raises by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import kvpaged
from bigdl_tpu.generate import sample_token_per_row
from bigdl_tpu.models.config import BLOCK_STRATEGIES
from bigdl_tpu.obs.scopes import scope
from bigdl_tpu.ops import routes
from bigdl_tpu.serving.hostrow import bits, expert_id_dtype


class _BlockPages(kvpaged.CacheKind):
    """KV pages (`kvpaged.KV_PAGES`) of a model generated by blocks: the
    same pool, table and prefill; prefixes unshared (a hit would have to end
    on a block boundary), and what a pass cannot serve refused by name."""

    label = "KV pages under block diffusion"
    share_prefixes = False
    needs_paged = ("{kind} is served from pages: a block is written through "
                   "its row's block table; use paged=True")
    refuses = kvpaged.not_wired(
        "R9", "quantize_kv", "speculative", "adapters",
        "prefill_chunk_tokens")

    def metrics(self, engine) -> list:
        sched = engine.blocks
        return [] if sched is None else sched.metrics()


CACHE_KIND = _BlockPages()


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockState:
    """What a row's block is, on the device, between two passes."""

    ids: jax.Array  # [B, b] int32: revealed ids, `mask_token_id` elsewhere
    revealed: jax.Array  # [B, b] bool (a prompt's tail counts as revealed)
    n_pass: jax.Array  # [B] int32: denoising passes the block has had


@dataclasses.dataclass
class _Row:
    """The host's half of a slot's block: what has been revealed and not
    left yet."""

    base: int  # the block's first position
    next_pos: int  # the position of the next token to leave
    block: int = 0  # blocks stored so far
    have: Any = None  # [b] bool
    toks: Any = None  # [b] int
    lps: Any = None  # [b] float


def reveal(logits, state: BlockState, key, sampling, steps: int,
           strategy: str, threshold: float):
    """One pass's reveal, on the device. `logits` [B, b, V] float32 of the
    block's positions; `sampling` the engine's per-row (temperature, top-k,
    top-p, do-sample); `steps`, `strategy` (one of `BLOCK_STRATEGIES`) and
    `threshold` static. Returns (x0 [B, b], log c [B, b], which positions
    this pass reveals [B, b], whether the pass found no mask left [B])."""
    B, b, V = logits.shape
    flat = logits.reshape(B * b, V)
    x0 = sample_token_per_row(
        flat, key, *(jnp.repeat(a, b) for a in sampling)).reshape(B, b)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lp = jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0] - lse
    masked = ~state.revealed
    store = ~jnp.any(masked, axis=1)
    at = jnp.arange(b)
    before = at[None, None, :] < at[None, :, None]  # [1, j, i]: i left of j
    if strategy == "sequential":
        chosen = jnp.sum(masked[:, None, :] & before, axis=-1) < 1
        return x0, lp, chosen & masked, store
    # a masked position's rank by confidence, the leftmost first of equals
    conf = jnp.where(masked, lp, -jnp.inf)
    better = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & before)
    rank = jnp.sum(better & masked[:, None, :], axis=-1)
    m_s = jnp.maximum(b // steps + (state.n_pass < b % steps), 1)[:, None]
    chosen = rank < m_s
    if strategy == "low_confidence_dynamic":
        passing = masked & (lp > jnp.log(jnp.float32(threshold)))
        enough = jnp.sum(passing, axis=1, keepdims=True) >= m_s
        chosen = jnp.where(enough, passing, chosen)
    return x0, lp, chosen & masked, store


class BlockScheduler:
    """The block loop of one engine: what its pass program and the program
    that opens a slot's first block compute (`pass_impl`, `arm_impl`: the
    engine jits and calls them), the device state between passes, and the
    host half that turns a pass's rows into tokens in position order."""

    def __init__(self, engine):
        cfg = engine.config
        self.engine = engine
        self.b = b = cfg.block_length
        what = f"{CACHE_KIND.label} ({cfg.model_type})"
        if engine.logprobs_top_k:
            raise NotImplementedError(
                f"logprobs_top_k is not wired for {what} yet: a pass "
                "scores b positions a row (ROADMAP R9)")
        if engine.gen.repetition_penalty != 1.0:
            raise NotImplementedError(
                f"a repetition penalty is not wired for {what} yet: "
                "positions are not revealed in order (ROADMAP R9)")
        if engine.page_size % b or engine.max_len % b:
            raise ValueError(
                f"page_size {engine.page_size} and max_len {engine.max_len} "
                f"must be multiples of block_length {b}: a block never "
                "straddles a page")
        if cfg.remasking_strategy not in BLOCK_STRATEGIES:
            raise ValueError(
                f"remasking_strategy must be one of {BLOCK_STRATEGIES}; got "
                f"{cfg.remasking_strategy!r}")
        self.steps = int(min(max(cfg.denoising_steps, 1), b))
        self.rows: list = [None] * engine.n_slots
        self.state = self._fresh_state()
        # /metrics: passes read, their live rows, positions revealed, tokens
        # that left, blocks stored
        self.passes = self.row_passes = 0
        self.tokens_revealed = self.tokens_emitted = self.blocks_stored = 0
        routes.note("blocks", f"b {b}",
                    f"{cfg.remasking_strategy} x{self.steps}, store own pass, "
                    f"attention {b * cfg.num_attention_heads // cfg.num_key_value_heads}"
                    " rows a KV head")

    def _fresh_state(self) -> BlockState:
        n, b = self.engine.n_slots, self.b
        return BlockState(
            ids=jnp.full((n, b), self.engine.config.mask_token_id, jnp.int32),
            revealed=jnp.zeros((n, b), jnp.bool_),
            n_pass=jnp.zeros((n,), jnp.int32))

    def reset(self) -> None:
        """After the pool was rebuilt: every block died with it."""
        self.state = self._fresh_state()
        self.rows = [None] * self.engine.n_slots

    # ---- what a request may say -------------------------------------------

    def refuses(self, req) -> Optional[str]:
        """The sentence that rejects `req` at submit, or None."""
        if req.repetition_penalty not in (None, 1.0):
            return ("a repetition penalty is not wired for generation by "
                    "blocks yet (ROADMAP R9)")
        return None

    def prefill_len(self, n_prompt: int) -> int:
        """Tokens of a prompt the admission's prefill stores: its whole
        blocks. The rest open the first block."""
        return n_prompt - n_prompt % self.b

    # ---- the device half ---------------------------------------------------

    def arm_impl(self, state: BlockState, slot, ids, revealed):
        with scope("engine"):
            return BlockState(
                ids=state.ids.at[slot].set(ids),
                revealed=state.revealed.at[slot].set(revealed),
                n_pass=state.n_pass.at[slot].set(0))

    def pass_impl(self, forward, params, state: BlockState, cache, key,
                  temp, topk, topp, dosample):
        """One pass over every slot's block: the forward (which writes the
        block's K and V at the row's `pos` from the ids as they are), the
        reveal, and for a row whose block held no mask the move to the next
        block. Packs what the host reads into one int32 row a slot."""
        eng, b = self.engine, self.b
        cfg = eng.config
        with scope("engine"):
            live = kvpaged.live_rows(cache)
        pos0 = cache.pos
        logits, cache, experts = eng._forward_routing(
            forward, params, state.ids, cache, "block", {})
        with scope("block.reveal"):
            x0, lp, shown, store = reveal(
                logits.astype(jnp.float32), state, key,
                (temp, topk, topp, dosample), self.steps,
                cfg.remasking_strategy, cfg.confidence_threshold)
        with scope("block.store"):
            # the forward advanced every row by b; only a row whose block is
            # stored moves on (an idle row, whose table maps nothing, never)
            move = store & live
            cache = dataclasses.replace(
                cache, pos=pos0 + jnp.where(move, b, 0))
            ids = jnp.where(shown, x0, state.ids)
            nxt = BlockState(
                ids=jnp.where(move[:, None], cfg.mask_token_id, ids),
                revealed=(state.revealed | shown) & ~move[:, None]
                & live[:, None],
                n_pass=jnp.where(move | ~live, 0, state.n_pass + 1))
        with scope("engine"):  # what the host reads
            out = [state.ids, x0, bits(lp), shown.astype(jnp.int32),
                   (~state.revealed).astype(jnp.int32),
                   store.astype(jnp.int32)[:, None], state.n_pass[:, None]]
            if experts is not None:  # [L, B, b, k] -> [B, L * b * k]
                out.append(jnp.swapaxes(experts, 0, 1).reshape(
                    x0.shape[0], -1).astype(jnp.int32))
            return nxt, jnp.concatenate(out, axis=1), cache

    # ---- an admission ------------------------------------------------------

    def arm(self, slot: int, req) -> tuple:
        """Open `slot`'s first block on the host: the prompt's tail beside
        its masks (the prefill has stored the whole blocks before it and
        the table's position stands at the block's first slot). Returns
        what `arm_impl` puts into the device state: (ids [b], revealed
        [b])."""
        b, cfg = self.b, self.engine.config
        tail = req.prompt[self.prefill_len(len(req.prompt)):]
        ids = np.full((b,), cfg.mask_token_id, np.int32)
        ids[:len(tail)] = tail
        shown = np.arange(b) < len(tail)
        n = len(req.prompt)
        self.rows[slot] = _Row(
            base=n - len(tail), next_pos=n, have=shown.copy(),
            toks=ids.copy(), lps=np.zeros((b,), np.float64))
        return ids, shown

    # ---- pages ahead of a pass ---------------------------------------------

    def need_tokens(self, slot: int, behind: bool) -> int:
        """Slots past the table's position that the row's next pass may
        write: its block, and the block after it where a pass is unread and
        will be found to have stored (the host knows: nothing was masked
        when it was enqueued). Never past the row's capacity, where no
        request has a position."""
        row = self.rows[slot]
        moved = behind and row is not None and bool(row.have.all())
        pages = self.engine.pages
        return min(self.b * (1 + moved),
                   self.engine.max_len - pages.pos[slot])

    # ---- the host half of a pass ------------------------------------------

    def read(self, fl, host: "np.ndarray", live: "np.ndarray",
             t_wait: Optional[float]) -> None:
        """Apply pass `fl` as fetched: note it, then for each live row
        take in what it revealed, let the tokens leave that are next in
        position order, and on a store move the row to its next block."""
        eng, b = self.engine, self.b
        cfg = eng.config
        ids_in, x0 = host[:, :b], host[:, b:2 * b]
        lps = np.ascontiguousarray(host[:, 2 * b:3 * b]).view(np.float32)
        shown = host[:, 3 * b:4 * b].astype(bool)
        masked = host[:, 4 * b:5 * b].astype(bool)
        store, n_pass = host[:, 5 * b].astype(bool), host[:, 5 * b + 1]
        experts = None
        if eng.moe_routing:  # [B, L * b * k] -> [L, B, b, k]
            experts = host[:, 5 * b + 2:].reshape(
                eng.n_slots, -1, b, cfg.num_experts_per_tok
            ).transpose(1, 0, 2, 3).astype(expert_id_dtype(cfg.num_experts))
            eng._moe_last = (experts.reshape(experts.shape[0], -1,
                                             experts.shape[-1]),
                             np.repeat(live, b))
        rows = [int(i) for i in np.nonzero(live)[0]]
        # what leaves, decided before anything moves: the span describes
        # the pass as it was dispatched
        leaving = {}
        for i in rows:
            row = self.rows[i]
            have = row.have | shown[i]
            n = 0
            while (row.next_pos + n < row.base + b
                   and have[row.next_pos + n - row.base]):
                n += 1
            leaving[i] = min(n, eng._slots[i].remaining)
        n_shown, n_stored = int(shown[live].sum()), int(store[live].sum())
        n_left = sum(leaving.values())
        self.passes += 1
        self.row_passes += len(rows)
        self.tokens_revealed += n_shown
        self.tokens_emitted += n_left
        self.blocks_stored += n_stored
        hist = np.bincount(n_pass[live & ~store], minlength=b)[:b]
        eng._note_decode_step(fl, live, t_wait, extra={
            "block_length": b, "passes": [int(c) for c in hist],
            "tokens_revealed": n_shown, "tokens_emitted": n_left,
            "blocks_stored": n_stored})
        with eng._phase("step.emit", seq=fl.seq):
            for i in rows:
                s, row = eng._slots[i], self.rows[i]
                req = s.req
                if not np.all(np.isfinite(lps[i][shown[i]])):
                    req.error = (
                        "non-finite logits in a block pass; request "
                        "quarantined (other slots unaffected)")
                    eng._finish(i, "error")
                    continue
                req.passes.append({
                    "block": row.block, "pass": int(n_pass[i]),
                    "base": row.base, "ids": ids_in[i].copy(),
                    "masked": masked[i].copy(),
                    "revealed": shown[i].copy(), "stored": bool(store[i]),
                    "experts": None if experts is None else experts[:, i]})
                row.have |= shown[i]
                row.toks[shown[i]] = x0[i][shown[i]]
                row.lps[shown[i]] = lps[i][shown[i]]
                if store[i]:
                    eng.pages.advance(i, b)
                    if experts is not None:
                        req.out_experts.extend(
                            experts[:, i, j] for j in range(b))
                for _ in range(leaving[i]):
                    j = row.next_pos - row.base
                    row.next_pos += 1
                    s.remaining -= 1
                    eng._emit(i, int(row.toks[j]), float(row.lps[j]))
                    if not eng.active[i] or eng._slots[i].req is not req:
                        break  # EOS or the count: the rest is dropped
                if store[i] and eng._slots[i].req is req:
                    row.base += b
                    row.block += 1
                    row.have[:] = False

    # ---- /metrics ----------------------------------------------------------

    def metrics(self) -> list:
        per = self.tokens_emitted / self.row_passes if self.row_passes else 0.0
        return [
            ("bigdl_tpu_block_passes_total", "counter", "passes of the "
             "block program read (each is one decode step)", self.passes),
            ("bigdl_tpu_block_tokens_revealed_total", "counter", "positions "
             "revealed by those passes in live rows", self.tokens_revealed),
            ("bigdl_tpu_blocks_stored_total", "counter", "blocks whose K and "
             "V were stored from their final ids", self.blocks_stored),
            ("bigdl_tpu_tokens_per_pass", "gauge", "tokens that left a live "
             "row and pass, since start", per)]
