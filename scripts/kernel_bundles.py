#!/usr/bin/env python3
"""What the TPU compiler SCHEDULES for one case of the packed-matmul kernel
bench: the case compiled for a described v5e (no chip) with libtpu's LLO dump
on, and the kernel's final VLIW bundles counted by unit in windows. By hand:

    JAX_PLATFORMS=cpu python scripts/kernel_bundles.py words s-p 32 4096 2048
    JAX_PLATFORMS=cpu python scripts/kernel_bundles.py prep d 32 4096 2048 --ops 0 900

A line is a window of `--window` bundles: how many of them hold an XLU
transpose push (`xpose`), a pop of the XLU's or the MXU's results (`vpop`), an
MXU push (`mxu`), and how many VALU operations, vector loads and vector stores
the window issues (a v5e bundle has four VALU slots: `valu` = 4 x window is a
loop VALU issue bounds). `--ops LO HI` prints the operations of bundles LO..HI
by name instead. The dump (hundreds of MB: `--keep` leaves it under `--dir`)
also holds the Mosaic passes (`mosaic/*post-apply-vector-layout*`: where a
relayout was put in) and the schedule at every LLO pass.

It counts bundles, not time: a stall of the memory system, of a sublane
shuffle or of instruction fetch is not in it. PR 62 read `r` (no relayout on
the VALU) and the skewed grid as gains here and as losses on the chip; only
the shorter scale decode moved both. libtpu aborts the process when the
compile is done (its dump of the host program fails): the table is printed
from the files it left, and one such process runs at a time on this machine.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BUNDLE = re.compile(
    r"\s*(0x[0-9a-f]+|\d+)\s+(?:[A-Z]{2}(?:,\s*[A-Z]{2})*)?\s*:\s*(?:>\s*)?\{(.*)\}")
_COMPILE = """
import sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {root!r} + "/scripts")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import qmatmul_kernel_bench as b
one = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
_, call, (block_m, _, _) = b.build({body!r}, {variant!r}, {M}, {K}, {O})
jax.jit(call).lower(
    jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
    *b.operands({M}, {K}, {O}, block_m, None, one,
                b.bits_of({body!r}, {variant!r}))).compile()
"""


def bundles(path):
    """[(index, [operation names])] of a `*final_bundles.txt`."""
    out = []
    for line in open(path):
        m = _BUNDLE.match(line)
        if m:
            out.append((int(m.group(1), 0),
                        re.findall(r"= (v[a-z0-9_.]+)", m.group(2))))
    return out


def unit(op):
    if "xpose" in op:
        return "xpose"
    if op.startswith("vmat"):
        return "mxu"
    for name in ("vpop", "vld", "vst"):
        if op.startswith(name):
            return name
    return "valu"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("body")
    ap.add_argument("variant")
    ap.add_argument("M", type=int)
    ap.add_argument("K", type=int)
    ap.add_argument("O", type=int)
    ap.add_argument("--window", type=int, default=300)
    ap.add_argument("--ops", type=int, nargs=2, metavar=("LO", "HI"))
    ap.add_argument("--dir", default=os.path.join(ROOT, ".scratch", "llo"))
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args()

    shutil.rmtree(args.dir, ignore_errors=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={args.dir} "
               "--xla_jf_dump_llo_text=true "
               f"--xla_mosaic_dump_to={args.dir}/mosaic")
    subprocess.run([sys.executable, "-c", _COMPILE.format(
        root=ROOT, body=args.body, variant=args.variant, M=args.M, K=args.K,
        O=args.O)], env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    found = [p for p in glob.glob(os.path.join(args.dir, "*final_bundles.txt"))
             if "qmatmul" in p and "schedule-analysis" not in p]
    if not found:
        print("no bundles were dumped: the case did not compile")
        return 1
    rows = bundles(found[0])
    print(f"{args.body} {args.variant} M={args.M} K={args.K} O={args.O}: "
          f"{len(rows)} bundles, all grid-step regions together")
    if args.ops:
        count = collections.Counter(
            op for i, ops in rows if args.ops[0] <= i < args.ops[1]
            for op in ops)
        for op, n in count.most_common(30):
            print(f"{n:7d} {op}")
    else:
        for at in range(0, len(rows), args.window):
            count = collections.Counter(
                unit(op) for _, ops in rows[at:at + args.window] for op in ops)
            print(f"{rows[at][0]:7d} " + " ".join(
                f"{k}={count[k]:5d}"
                for k in ("xpose", "vpop", "mxu", "valu", "vld", "vst")))
    if not args.keep:
        shutil.rmtree(args.dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
