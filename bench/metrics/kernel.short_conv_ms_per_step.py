"""Device time of the gated short-convolution operators' OWN arithmetic per
decode step: everything of the operators but their two packed projections
(the gate B * x, the three-tap convolution over the row's tail, the tail's
update, C * c), all convolution layers. There is no kernel for it, so it is
what XLA runs under the mixer's scope `mamba2` in `engine_decode`: the
scope's row of the account (`bench/reduce/scopes.py`) less its Mosaic kernels
(`qmatmul`: the projections), per execution wholly inside the traced seconds,
mean, ms. The scope `short_conv` stands inside the mixer's and is NOT what is
summed: a fusion is its ROOT's scope, and XLA roots the gate's and the taps'
fusions anywhere inside the mixer (PR 61 read 0.086 ms under `short_conv`
alone where the mixer's XLA operations took 0.134). None for a program
without the mixer's scope, or where the trace's metadata cannot be read."""

from bench.reduce import scopes

ENTRIES = ("engine",)
SCOPE, PROGRAM = "mamba2", "engine_decode"


def read(run):
    acc = scopes.account(run)
    if acc is None or not acc.n.get(PROGRAM):
        return None
    row = acc.rows[PROGRAM].get(SCOPE)
    if row is None or not row.xla_s:
        return None
    return row.xla_s * 1e3 / acc.n[PROGRAM]
