"""Tokens DELIVERED a live row and pass of a model generated by diffusion
over blocks: `tokens_emitted / occupancy` of each `decode_step` span that
carries `block_length`, mean over the window's spans. A block of b positions
revealed over s denoising passes and stored by one more gives b / (s + 1)
(1.33 at b 4, s 2); with the store folded into the next block's first pass it
would give b / s. Tokens leave in position order and a request ends inside a
block, so what a pass reveals and what leaves differ by the span. None for a
program whose steps are not passes."""

ENTRIES = ("engine",)


def read(run):
    s = [a for _, _, a in run.span_list("decode_step")
         if a.get("block_length") and a.get("occupancy")
         and "tokens_emitted" in a]
    if not s:
        return None
    return sum(a["tokens_emitted"] / a["occupancy"] for a in s) / len(s)
