"""How unevenly a decode step's assignments fall on the experts: the busiest
(layer, expert) pair's assignments over the mean per pair, averaged over the
window's `decode_step` spans (1.0 = perfectly even). A property of the
router and the traffic, not of the kernel: it says whether a cell with
skewed routing would differ from this one. None where the program's spans
lack the arguments (a dense model, or a program without them)."""

ENTRIES = ("engine",)


def read(run):
    s = [a for _, _, a in run.span_list("decode_step")
         if a.get("moe_experts") and a.get("moe_assignments")]
    if not s:
        return None
    return sum(a["moe_max_expert_load"] * a["moe_experts"]
               / a["moe_assignments"] for a in s) / len(s)
