"""bigdl-tpu command line.

Role-equivalent of the reference's `llm-cli` / `llm-chat` shell dispatch
(cli/llm-cli:25-57 in /root/reference — there it picks a per-ISA C++
binary; here every path is the same XLA program) plus `llm_convert`
(convert_model.py:31).

    python -m bigdl_tpu.cli convert  <hf_dir> -o <out_dir> --qtype sym_int4
    python -m bigdl_tpu.cli generate <model_dir> -p "..." -n 64
    python -m bigdl_tpu.cli serve    <model_dir> --port 8000
    python -m bigdl_tpu.cli bench    <model_dir>
    python -m bigdl_tpu.cli chat     <model_dir>
    python -m bigdl_tpu.cli verify   <ckpt_dir | ckpt.npz>
    python -m bigdl_tpu.cli train-status <ckpt_dir>
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _load(path: str, qtype):
    """qtype=None means: native formats for .gguf, sym_int4 for HF dirs."""
    from bigdl_tpu.api import AutoModelForCausalLM

    if path.endswith(".gguf"):
        return AutoModelForCausalLM.from_gguf(path, qtype=qtype)
    import os

    if os.path.exists(os.path.join(path, "bigdl_tpu_config.json")):
        return AutoModelForCausalLM.load_low_bit(path)
    return AutoModelForCausalLM.from_pretrained(
        path, load_in_low_bit=qtype or "sym_int4"
    )


def _tokenizer(path: str):
    """The model directory's tokenizer, or None (token-id prompts still
    work) when transformers is not installed or the directory holds no
    tokenizer — said on stderr, never silently."""
    try:
        from transformers import AutoTokenizer
    except ImportError:
        print("no tokenizer: transformers is not installed", file=sys.stderr)
        return None
    try:
        return AutoTokenizer.from_pretrained(path)
    except (OSError, ValueError) as e:
        print(f"no tokenizer loaded from {path}: {e}", file=sys.stderr)
        return None


def _gen_text(model, tok, ids, max_new_tokens, temperature):
    """Shared generate path for the one-shot and chat commands: greedy
    or sampled, EOS/pad TRIMMED before decode (generate_tokens pads the
    fixed [B, max_new] output after EOS — leaking pads corrupts decoded
    text and, in chat mode, every later turn's history)."""
    eos = tok.eos_token_id if tok else None
    out = model.generate(
        [ids], max_new_tokens=max_new_tokens,
        do_sample=temperature > 0, temperature=max(temperature, 1e-5),
        eos_token_id=eos,
    )
    toks = out[0].tolist()
    if eos is not None and eos in toks:
        # cut at EOS: everything after is pad fill (generate_tokens pads
        # the fixed output window) — stripping pad VALUES instead would
        # eat legitimate id-0 tokens when EOS never fired
        toks = toks[: toks.index(eos) + 1]
    return toks, (tok.decode(toks, skip_special_tokens=True)
                  if tok else str(toks))


def cmd_convert(args):
    # gguf export re-encodes weights into the gguf payload type: HF dirs
    # load at bf16 unless the user asked for a low-bit intermediate (or
    # the file would claim q8_0 precision with sym_int4 accuracy);
    # .gguf inputs keep their native per-tensor formats (qtype=None)
    load_q = args.qtype
    if args.format == "gguf" and not args.model.endswith(".gguf"):
        load_q = args.qtype or "bf16"
    model = _load(args.model, load_q)
    if args.format == "gguf":
        from bigdl_tpu.convert.gguf_export import export_gguf
        from bigdl_tpu.models import get_family

        # loaders merge qkv/gate-up by default; split back for export
        # (layouts loaded via from_gguf/low_bit arrive merged too)
        params = model.params
        fam = get_family(model.config.model_type)
        if hasattr(fam, "unmerge_fused_params"):
            params = fam.unmerge_fused_params(params, model.config)
        out = args.output if args.output.endswith(".gguf") \
            else args.output + ".gguf"
        export_gguf(model.config, params, out,
                    qtype=args.gguf_qtype)
        print(f"exported {args.gguf_qtype} gguf to {out}")
        return
    model.save_low_bit(args.output)
    print(f"saved {args.qtype} model to {args.output}")


def cmd_generate(args):
    model = _load(args.model, args.qtype)
    tok = _tokenizer(args.model)
    if tok is None:
        ids = [int(t) for t in args.prompt.split()]
    else:
        ids = list(tok(args.prompt)["input_ids"])
    t0 = time.time()
    toks, text = _gen_text(model, tok, ids, args.max_new_tokens,
                           args.temperature)
    dt = time.time() - t0
    print(text)
    print(
        f"[{len(toks)} tokens in {dt:.2f}s — {1000 * dt / max(len(toks), 1):.1f} ms/token]",
        file=sys.stderr,
    )


def cmd_chat(args):
    """Interactive chat REPL — the reference's `llm-chat` wrapper
    (cli/llm-cli dispatches to main-<family> binaries; here the same
    jitted decode drives a tokenizer chat template when available).

    Turns run through an incremental ChatSession (delta prefill — the
    cache persists across turns, unlike the reference's full-history
    re-prefill); --streaming-window makes the conversation unbounded
    via attention sinks. Families with custom cache adapters fall back
    to one-shot generation."""
    model = _load(args.model, args.qtype)
    if args.adapter:
        # one-tenant chat: fold the adapter into the loaded params
        # (train/qlora.merge_lora) — the REPL serves a single user, so
        # the multi-tenant epilogue machinery would be pure overhead
        from bigdl_tpu.serving.adapters import load_adapter
        from bigdl_tpu.train.qlora import merge_lora

        lora, meta = load_adapter(args.adapter)
        model.params = merge_lora(model.params, lora)
        print(f"note: merged adapter {args.adapter} "
              f"(rank {meta.get('rank')})", file=sys.stderr)
    tok = _tokenizer(args.model)
    history: list[dict] = []

    def new_session():
        from bigdl_tpu.chat import ChatSession

        streaming = ((args.streaming_sink, args.streaming_window)
                     if args.streaming_window else None)
        return ChatSession(model, max_len=args.max_len, streaming=streaming)

    session = None
    consumed: list[int] = []
    try:
        session = new_session()
    except NotImplementedError as e:
        print(f"note: {e}; using one-shot generation", file=sys.stderr)
    templated = tok is not None and getattr(tok, "chat_template", None)
    if args.system:
        if not templated:
            print("warning: --system needs a tokenizer chat template; "
                  "ignored for this model", file=sys.stderr)
        else:
            history.append({"role": "system", "content": args.system})
    print("bigdl-tpu chat — empty line or /exit quits", file=sys.stderr)
    while True:
        try:
            line = input("you> ")
        except (EOFError, KeyboardInterrupt):
            break
        if not line.strip() or line.strip() == "/exit":
            break
        if templated:
            history.append({"role": "user", "content": line})
            ids = list(tok.apply_chat_template(
                history, add_generation_prompt=True
            ))
        elif tok is not None:
            ids = list(tok(line)["input_ids"])
        else:  # no tokenizer: whitespace token ids (testing)
            ids = [int(t) for t in line.split()]
        if session is not None:
            eos = tok.eos_token_id if tok else None
            if ids[: len(consumed)] == consumed and len(ids) > len(consumed):
                delta = ids[len(consumed):]
            else:
                # the template rewrote earlier tokens (or this is the
                # first turn): reset the session (keeps compiled
                # programs) and replay the full ids
                session.reset()
                consumed, delta = [], ids
            try:
                toks = session.send(
                    delta, args.max_new_tokens, eos,
                    temperature=args.temperature,
                )
            except ValueError as e:  # window/max_len overflow
                print(f"note: {e}; restarting context", file=sys.stderr)
                session.reset()
                consumed = []
                try:
                    toks = session.send(ids, args.max_new_tokens, eos,
                                        temperature=args.temperature)
                except ValueError as e2:
                    # even a fresh context cannot fit this turn — tell
                    # the user and keep the REPL alive
                    print(f"error: {e2}", file=sys.stderr)
                    session.reset()
                    continue
            consumed = ids + toks
            text = (tok.decode(toks, skip_special_tokens=True)
                    if tok else str(toks))
        else:
            _, text = _gen_text(model, tok, ids, args.max_new_tokens,
                                args.temperature)
        print(f"bot> {text}")
        if templated:
            history.append({"role": "assistant", "content": text})


def cmd_serve(args):
    from bigdl_tpu.serving.api_server import ApiServer

    from bigdl_tpu.generate import GenerationConfig

    if args.speculative:
        # the sym_int4 self-draft needs a higher-precision target; fail
        # fast BEFORE the (slow) model load, and default the target to
        # bf16 when no qtype was asked for
        if args.qtype is None:
            print("--speculative: loading target as bf16 (self-draft is "
                  "sym_int4); pass -q to override")
            args.qtype = "bf16"
        else:
            from bigdl_tpu.quant.qtypes import resolve_qtype

            try:
                dense = resolve_qtype(args.qtype).is_dense
            except ValueError:
                dense = False
            if not dense:
                raise SystemExit(
                    f"--speculative needs an unquantized target "
                    f"(-q bf16/fp16); got -q {args.qtype}"
                )
    model = _load(args.model, args.qtype)
    # consumed by TpuModel.to_mesh() whenever the model is later sharded
    # over a tp axis (parallel/qcollectives.py wire format for the
    # row-parallel epilogue all-reduces; "none" keeps GSPMD's exact psum)
    model.default_comm_qtype = args.comm_qtype
    tok = _tokenizer(args.model)
    gen = GenerationConfig(
        eos_token_id=(tok.eos_token_id if tok is not None else None)
    )
    adapters = None
    if args.adapter_dir or args.adapter_budget_mb or args.adapters:
        # any adapter flag enables the registry: --adapter-budget-mb
        # without a dir still serves explicit-path POST /adapters/load,
        # and must not be silently ignored
        from bigdl_tpu.serving.adapters import AdapterRegistry

        adapters = AdapterRegistry(
            dir=args.adapter_dir,
            budget_bytes=(args.adapter_budget_mb * (1 << 20)
                          if args.adapter_budget_mb else None),
        )
        for spec in args.adapters or []:
            name, _, path = spec.partition("=")
            desc = adapters.load(name, path=path or None, pin=True)
            print(f"pinned adapter {desc['name']} (rank {desc['rank']})",
                  file=sys.stderr)
    embedder = None
    if args.embedder:
        from bigdl_tpu.convert.hf import open_checkpoint
        from bigdl_tpu.models import bert as B

        with open(os.path.join(args.embedder, "config.json")) as f:
            bcfg = B.BertConfig.from_hf_config(json.load(f))
        get = open_checkpoint(args.embedder)
        embedder = (bcfg, B.params_from_hf(bcfg, get), _tokenizer(args.embedder))
    server = ApiServer(
        model, tokenizer=tok, host=args.host,
        port=args.port, n_slots=args.slots, max_len=args.max_len, gen=gen,
        paged=args.paged,
        prefill_chunk_tokens=args.prefill_chunk_tokens,
        speculative=args.speculative,
        draft_k=args.draft_k, adaptive_draft=args.adaptive_draft,
        embedder=embedder, truncate_prompts=args.truncate_prompts,
        logprobs_top_k=args.logprobs_top_k,
        tracing=args.trace, trace_capacity=args.trace_capacity,
        request_log=args.request_log, adapters=adapters,
    )
    server.start()
    server.install_signal_handlers()  # SIGTERM -> drain, flush, exit 0
    print(f"bigdl-tpu serving {args.model} on {args.host}:{server.port}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        # ^C gets the same drain as SIGTERM: in-flight requests finish
        # (bounded by request_timeout_s), journal flushed + compacted
        server.shutdown(graceful=True)


def cmd_fastchat_worker(args):
    from bigdl_tpu.serving.fastchat_worker import FastChatWorker

    model = _load(args.model, args.qtype)
    worker = FastChatWorker(
        model, tokenizer=_tokenizer(args.model),
        controller_addr=args.controller_address,
        worker_addr=args.worker_address,
        model_names=(args.model_names.split(",") if args.model_names
                     else None),
        host=args.host, port=args.port, n_slots=args.slots,
        max_len=args.max_len, paged=args.paged,
    )
    worker.start(register=args.controller_address is not None)
    print(f"fastchat worker {worker.worker_id} serving {args.model} "
          f"at {worker.worker_addr}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        worker.shutdown()


def cmd_fetch_iq_tables(args):
    from bigdl_tpu.quant import iq_quants

    url = args.url or iq_quants.DEFAULT_TABLES_URL
    tables = iq_quants.fetch_tables(url=url)
    print(f"cached {sorted(tables)} -> {iq_quants._cache_path()}")


def cmd_txt2img(args):
    from bigdl_tpu.models.sd import load_diffusers_pipeline
    from bigdl_tpu.utils.png import write_png

    pipe = load_diffusers_pipeline(args.model, qtype=args.qtype)

    def as_prompt(text):
        if text is None:
            return None
        toks = text.split()
        if toks and all(t.isdigit() for t in toks):
            return [int(t) for t in toks]  # raw CLIP ids (no tokenizer)
        return text

    imgs = pipe(as_prompt(args.prompt),
                negative_prompt=as_prompt(args.negative),
                height=args.size, width=args.size, num_steps=args.steps,
                guidance_scale=args.guidance, seed=args.seed)
    write_png(args.output, imgs[0])
    print(f"wrote {args.output} ({args.size}x{args.size}, "
          f"{args.steps} steps, cfg {args.guidance})")


def cmd_verify(args):
    """Offline integrity + numerical validation (docs/durability.md):
    `full` mode — sizes/shapes/crc32/sha256 against the artifact's
    integrity manifest plus NaN/inf and scale-range scans — with a
    per-tensor report. Exit code 1 on ANY finding, so CI and operators
    can gate a deploy on a clean checkpoint. Accepts a save_low_bit
    directory or a train-checkpoint .npz (a rotation directory verifies
    every candidate)."""
    path = args.path
    reports = []
    if os.path.isdir(path):
        if os.path.exists(os.path.join(path, "bigdl_tpu_config.json")):
            from bigdl_tpu.convert.low_bit import verify_low_bit

            reports.append(verify_low_bit(path))
        else:
            from bigdl_tpu.train.checkpoint import (
                list_train_checkpoints, verify_train_checkpoint,
            )

            ckpts = list_train_checkpoints(path)
            if not ckpts:
                raise SystemExit(
                    f"{path}: neither a low-bit checkpoint "
                    "(bigdl_tpu_config.json) nor a train-checkpoint "
                    "rotation directory (ckpt-*.npz)"
                )
            reports += [verify_train_checkpoint(p) for p in ckpts]
    elif path.endswith(".npz"):
        from bigdl_tpu.train.checkpoint import verify_train_checkpoint

        reports.append(verify_train_checkpoint(path))
    else:
        raise SystemExit(
            f"{path}: expected a checkpoint directory or a .npz file"
        )
    ok = True
    for rep in reports:
        print(rep.format())
        ok = ok and rep.ok
    if not ok:
        raise SystemExit(1)
    print("OK")


def cmd_train_status(args):
    """Operator view of a training run's checkpoint dir (pairs with
    `bigdl-tpu verify`, which does the full per-tensor audit): rotation
    inventory with fast integrity verdicts, the last-good (newest
    loadable) step a restart would resume from, and the tail of the
    supervisor's structured event log. Exit 1 when checkpoints exist
    but NONE is loadable — a restart would silently start from step 0."""
    import glob as _glob

    from bigdl_tpu.train.checkpoint import (
        inspect_train_checkpoints_dir, list_train_checkpoints,
    )
    from bigdl_tpu.train.supervisor import EventLog

    d = args.ckpt_dir
    if not os.path.isdir(d):
        raise SystemExit(f"{d}: not a checkpoint directory")
    infos = inspect_train_checkpoints_dir(d)
    if not infos:
        print(f"{d}: no rotated checkpoints (ckpt-*.npz)")
    else:
        print(f"{d}: {len(infos)} rotated checkpoint(s), newest first")
        for info in infos:
            status = "ok" if info["ok"] else f"CORRUPT ({info['detail']})"
            size = info["size"]
            mtime = (time.strftime("%Y-%m-%d %H:%M:%S",
                                   time.localtime(info["mtime"]))
                     if info["mtime"] else "?")
            print(f"  {os.path.basename(info['path'])}  "
                  f"step={info['step']}  {size or '?'}B  {mtime}  {status}")
        good = [i for i in infos if i["ok"]]
        if good:
            print(f"last-good step: {good[0]['step']} "
                  f"({os.path.basename(good[0]['path'])})")
        else:
            print("last-good step: NONE — every candidate is corrupt; "
                  "a restart would begin from scratch")
    legacy = os.path.join(d, "train_state.npz")
    if os.path.exists(legacy):
        print(f"legacy single-file checkpoint present: {legacy}")
    events = sorted(_glob.glob(os.path.join(d, "supervisor_events*.jsonl")))
    for ev_path in events:
        # run provenance: the newest `backward` event says which dx path
        # the step function was traced with (fused Pallas vs XLA remat) —
        # scan deeper than the display tail so an old flip isn't missed
        bwd = [e for e in EventLog.tail(ev_path, n=10000)
               if e.get("kind") == "backward"]
        if bwd:
            print(f"backward path: {bwd[-1].get('path')} "
                  f"(recorded at step {bwd[-1].get('step')})")
        tail = EventLog.tail(ev_path, n=args.events)
        print(f"\n{os.path.basename(ev_path)} (last {len(tail)} events):")
        for e in tail:
            ts = time.strftime("%H:%M:%S", time.localtime(e.get("ts", 0)))
            extra = {k: v for k, v in e.items()
                     if k not in ("ts", "step", "kind")}
            print(f"  {ts}  step {e.get('step'):>8}  {e.get('kind'):<16}"
                  + (f" {extra}" if extra else ""))
    if not events:
        print("no supervisor event log (pre-supervisor run, or the "
              "trainer was driven without TrainSupervisor)")
    if infos and not any(i["ok"] for i in infos):
        raise SystemExit(1)


def cmd_trace(args):
    """Observability toolbox against a live server or a dumped trace
    (docs/observability.md):

        bigdl-tpu trace dump http://127.0.0.1:8000 -o trace.json
        bigdl-tpu trace summarize trace.json
        bigdl-tpu trace profile-start http://127.0.0.1:8000 --logdir /tmp/prof
        bigdl-tpu trace profile-stop  http://127.0.0.1:8000

    `dump` fetches the server's span ring buffer as Chrome trace-event
    JSON (loads directly in Perfetto); `summarize` reduces a trace file
    to a per-phase latency table; `profile-start`/`profile-stop` drive
    the server's guarded jax.profiler window."""
    if args.action == "summarize":
        from bigdl_tpu.obs.tracing import format_summary, summarize_trace

        with open(args.target, encoding="utf-8") as f:
            trace = json.load(f)
        print(format_summary(summarize_trace(trace)))
        return
    import urllib.error
    import urllib.request

    base = args.target.rstrip("/")

    def fetch(req_or_path):
        req = req_or_path if not isinstance(req_or_path, str) \
            else base + req_or_path
        path = req if isinstance(req, str) else req.full_url
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            body = e.read().decode(errors="replace")
            raise SystemExit(f"{path} -> HTTP {e.code}: {body}")
        except urllib.error.URLError as e:
            raise SystemExit(f"cannot reach {path}: {e.reason}")

    def post(path, payload):
        return json.loads(fetch(urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )))

    if args.action == "dump":
        data = fetch("/debug/trace")
        try:
            n = len(json.loads(data).get("traceEvents", []))
        except json.JSONDecodeError:
            raise SystemExit(
                f"{base}/debug/trace returned non-JSON — is this a "
                "bigdl-tpu server?"
            )
        out = args.output
        from bigdl_tpu.utils.durability import atomic_write

        atomic_write(out, lambda f: f.write(data))
        print(f"wrote {n} trace events to {out} — open in Perfetto "
              "(https://ui.perfetto.dev) or chrome://tracing")
    elif args.action == "profile-start":
        if not args.logdir:
            raise SystemExit("profile-start needs --logdir")
        out = post("/debug/profiler", {"action": "start",
                                       "logdir": args.logdir})
        print(f"profiler window open -> {out['logdir']}")
    elif args.action == "profile-stop":
        out = post("/debug/profiler", {"action": "stop"})
        print(f"profiler window closed after {out.get('seconds')}s; "
              f"inspect {out['logdir']} with TensorBoard/XProf")


def cmd_adapters(args):
    """Multi-tenant LoRA adapter lifecycle (docs/serving.md §7) —
    against a live server, or a local artifact:

        bigdl-tpu adapters list   http://127.0.0.1:8000
        bigdl-tpu adapters load   http://127.0.0.1:8000 my-tenant [--path p] [--pin]
        bigdl-tpu adapters unload http://127.0.0.1:8000 my-tenant
        bigdl-tpu adapters inspect path/to/adapter.npz

    `inspect` verifies the artifact offline (full integrity mode) and
    prints its rank/targets/size; the server actions drive the
    registry's load/unload endpoints."""
    if args.action == "inspect":
        from bigdl_tpu.serving.adapters import load_adapter
        from bigdl_tpu.utils.durability import IntegrityError

        try:
            lora, meta = load_adapter(args.target, verify="full")
        except FileNotFoundError:
            raise SystemExit(f"{args.target}: no such adapter artifact")
        except IntegrityError as e:
            # the whole point of inspect is catching this: report the
            # structured finding and exit 1, like `bigdl-tpu verify`
            raise SystemExit(f"FAILED {e}")
        from bigdl_tpu.serving.adapters import lora_nbytes

        print(json.dumps({
            "path": args.target, "rank": meta.get("rank"),
            "scale": meta.get("scale"), "targets": meta.get("targets"),
            "nbytes": lora_nbytes(lora), "verified": "full",
        }, indent=2))
        return
    import urllib.error
    import urllib.request

    base = args.target.rstrip("/")

    def call(path, payload=None):
        req = (base + path if payload is None else urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        ))
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            body = e.read().decode(errors="replace")
            raise SystemExit(f"{base}{path} -> HTTP {e.code}: {body}")
        except urllib.error.URLError as e:
            raise SystemExit(f"cannot reach {base}{path}: {e.reason}")

    if args.action == "list":
        out = call("/adapters")
        print(json.dumps(out, indent=2))
    elif args.action == "load":
        if not args.name:
            raise SystemExit("adapters load needs a NAME")
        payload = {"name": args.name, "pin": args.pin}
        if args.path:
            payload["path"] = args.path
        out = call("/adapters/load", payload)
        a = out["adapter"]
        print(f"loaded {a['name']} (rank {a['rank']}, "
              f"{a['nbytes']}B{', pinned' if a['pinned'] else ''})")
    elif args.action == "unload":
        if not args.name:
            raise SystemExit("adapters unload needs a NAME")
        out = call("/adapters/unload", {"name": args.name})
        print(f"unloaded {out['adapter']['name']}")


def cmd_simserve(args):
    """Simulated-clock serving benchmark (docs/benchmarking.md): drive
    the real engine with a seeded synthetic trace under a virtual clock
    and a roofline cost model — engine-level throughput / TTFT / p99 /
    preemption + shed numbers with ZERO devices.

        bigdl-tpu simserve --trace poisson --seed 0
        bigdl-tpu simserve --trace overload -o report.json
        bigdl-tpu simserve --trace-file banked.jsonl

    Prints exactly one JSON report line (sorted keys: two identical
    invocations are byte-identical). `--save-trace` banks the generated
    arrival trace as replayable crc'd JSONL."""
    import jax

    # zero-device contract: the simulator never claims a chip, whatever
    # the environment says (a chip belongs to one process at a time,
    # and this one needs none)
    jax.config.update("jax_platforms", "cpu")
    from bigdl_tpu.sim.engine_driver import (
        SCENARIOS, SimDriver, default_cost_model, report_json,
    )
    from bigdl_tpu.sim.traces import Trace, named_trace

    if args.trace_file:
        trace = Trace.load(args.trace_file)
        sim = SCENARIOS.get(trace.name) or SCENARIOS["poisson"]
    else:
        trace = named_trace(args.trace, seed=args.seed)
        sim = SCENARIOS[args.trace]
    if args.save_trace:
        trace.save(args.save_trace)
        print(f"saved {len(trace.arrivals)}-arrival trace to "
              f"{args.save_trace}", file=sys.stderr)
    if args.speculative or args.draft_k is not None:
        # flag overrides on top of the named scenario: any mix can run
        # through draft+verify rounds (adapter mixes draft with the
        # base and verify with the adapter applied — engine.py §spec)
        import dataclasses as _dc

        sim = _dc.replace(
            sim, speculative=True,
            draft_k=sim.draft_k if args.draft_k is None else args.draft_k,
        )
    driver = SimDriver(trace, sim=sim,
                       cost=default_cost_model(
                           hbm_gbps=args.hbm_gbps, ici_gbps=args.ici_gbps,
                           tp=args.tp, comm_qtype=args.comm_qtype))
    report = driver.run()
    line = report_json(report)
    if args.output:
        from bigdl_tpu.utils.durability import atomic_write

        atomic_write(args.output,
                     lambda f: f.write((line + "\n").encode("utf-8")))
        print(f"wrote report to {args.output}", file=sys.stderr)
    print(line)


def cmd_lint(args):
    """graftlint: the AST-based invariant gate (docs/static-analysis.md).

        bigdl-tpu lint                     # whole bigdl_tpu package
        bigdl-tpu lint bigdl_tpu/serving   # a subtree / single file
        bigdl-tpu lint --rules WCT001,PAGE002
        bigdl-tpu lint --format github     # ::error CI annotations
        bigdl-tpu lint --write-baseline    # grandfather current findings
        bigdl-tpu lint --update-baseline   # drop stale, keep justifications

    Exit 0 = clean, 1 = non-baselined findings or stale baseline
    entries, 2 = config error. Deliberately jax-free: scripts/ci.sh
    --lint asserts jax never entered sys.modules during a run."""
    from bigdl_tpu.analysis import core as lint_core

    if args.list_rules:
        for c in lint_core.default_checks():
            print(f"{c.rule}  {c.description}")
        raise SystemExit(0)
    write_to = None
    if args.write_baseline:
        write_to = args.baseline or lint_core.DEFAULT_BASELINE
    raise SystemExit(lint_core.run(
        paths=args.paths or None,
        baseline_path=args.baseline,
        rules=args.rules.split(",") if args.rules else None,
        write_baseline_path=write_to,
        fmt=args.format,
        update_baseline=args.update_baseline,
    ))


def cmd_bench(args):
    model = _load(args.model, args.qtype)
    n_in, n_out = args.in_len, args.out_len
    ids = list(range(1, n_in + 1))
    # warm BOTH jit specializations (max_new_tokens is static) before
    # any timing, or the first-token run would include a compile
    model.generate([ids], max_new_tokens=1)
    model.generate([ids], max_new_tokens=n_out)
    t1 = time.time()
    model.generate([ids], max_new_tokens=1)
    first = time.time() - t1
    t0 = time.time()
    model.generate([ids], max_new_tokens=n_out)
    dt = max((time.time() - t0 - first) / max(n_out - 1, 1), 1e-5) * 1000
    print(json.dumps({
        "metric": "decode_latency", "value": round(dt, 2),
        "unit": "ms/token", "first_token_ms": round(first * 1000, 1),
        "protocol": f"in{n_in}-out{n_out}",
    }))


def main(argv=None):
    p = argparse.ArgumentParser(prog="bigdl-tpu")
    # -q works BOTH before the subcommand (top-level, original position)
    # and after it (documented position): the subparser copy defaults to
    # SUPPRESS so it never clobbers a top-level value
    p.add_argument("-q", "--qtype", default=None,
                   help="sym_int4 (HF default) / q4_k_m / ... ; gguf keeps "
                        "native formats unless set")
    qp = argparse.ArgumentParser(add_help=False)
    qp.add_argument("-q", "--qtype", default=argparse.SUPPRESS,
                    help=argparse.SUPPRESS)
    sub = p.add_subparsers(dest="cmd", required=True)

    c = sub.add_parser("convert", help="quantize + save_low_bit / gguf export",
                       parents=[qp])
    c.add_argument("model")
    c.add_argument("-o", "--output", required=True)
    c.add_argument("-f", "--format", choices=("low_bit", "gguf"),
                   default="low_bit",
                   help="low_bit: our reload format; gguf: llama.cpp file")
    c.add_argument("--gguf-qtype", default="q8_0",
                   # literal: keep CLI startup free of convert imports
                   # (must mirror gguf_export._GGML_FOR_QTYPE)
                   choices=("bf16", "f16", "f32", "q2_k", "q3_k", "q4_0",
                            "q4_k", "q5_k", "q6_k", "q8_0"),
                   help="gguf payload type")
    c.set_defaults(fn=cmd_convert)

    g = sub.add_parser("generate", help="one-shot generation", parents=[qp])
    g.add_argument("model")
    g.add_argument("-p", "--prompt", required=True)
    g.add_argument("-n", "--max-new-tokens", type=int, default=64)
    g.add_argument("-t", "--temperature", type=float, default=0.0)
    g.set_defaults(fn=cmd_generate)

    s = sub.add_parser("serve", help="OpenAI-compatible server", parents=[qp])
    s.add_argument("model")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--slots", type=int, default=8)
    s.add_argument("--max-len", type=int, default=2048)
    s.add_argument("--speculative", action="store_true",
                   help="in-engine speculative decoding (sym_int4 "
                        "self-draft; needs an unquantized model load)")
    s.add_argument("--draft-k", type=int, default=4)
    s.add_argument("--adaptive-draft", action="store_true",
                   help="steer draft length from recent acceptance "
                        "(ladder of compiled K programs)")
    s.add_argument("--embedder", default=None,
                   help="bert checkpoint dir: enables POST /v1/embeddings")
    s.add_argument("--truncate-prompts", action="store_true",
                   help="keep the tail of over-long prompts instead of "
                        "rejecting them with 400")
    s.add_argument("--logprobs-top-k", type=int, default=0,
                   help="serve OpenAI top_logprobs with up to N "
                        "alternatives per token")
    s.add_argument("--paged", action="store_true",
                   help="paged KV pool + radix prefix caching")
    s.add_argument("--prefill-chunk-tokens", type=int, default=None,
                   help="paged: interleave prompt prefill with decode "
                        "in chunks of at most N tokens, bounding the "
                        "running batch's stall to one chunk per step "
                        "(docs/serving.md §6; default: monolithic)")
    s.add_argument("--trace", action="store_true",
                   help="record request-lifecycle spans into a bounded "
                        "ring buffer (dump: `bigdl-tpu trace dump`, or "
                        "GET /debug/trace; docs/observability.md)")
    s.add_argument("--trace-capacity", type=int, default=65536,
                   help="span ring-buffer bound (newest kept)")
    s.add_argument("--request-log", default=None,
                   help="append one derived-timings JSONL record per "
                        "finished request (queue wait, TTFT, "
                        "time-per-output-token, preempted time)")
    s.add_argument("--adapter-dir", default=None,
                   help="multi-tenant LoRA: directory of <name>.npz "
                        "adapter artifacts; requests may then carry "
                        '"adapter": "<name>" and the /adapters '
                        "lifecycle endpoints come up (docs/serving.md §7)")
    s.add_argument("--adapter-budget-mb", type=int, default=None,
                   help="host-RAM budget for resident adapters; LRU "
                        "eviction above it (default: unbounded; "
                        "enables the registry even without "
                        "--adapter-dir — load via POST /adapters/load "
                        "with an explicit path)")
    s.add_argument("--adapters", action="append", default=None,
                   metavar="NAME[=PATH]",
                   help="preload + pin an adapter at startup "
                        "(repeatable; PATH defaults to "
                        "<adapter-dir>/NAME.npz)")
    s.add_argument("--comm-qtype", default="none",
                   choices=("none", "int8", "fp8_e4m3"),
                   help="multi-chip: quantize TP collectives to this "
                        "block-scaled wire format (parallel/"
                        "qcollectives.py; picked up by to_mesh(); "
                        "'none' = exact fp32/bf16 ICI traffic)")
    s.set_defaults(fn=cmd_serve)

    fw = sub.add_parser("fastchat-worker",
                        help="FastChat model-worker (register + heartbeat "
                             "+ worker_generate_stream)", parents=[qp])
    fw.add_argument("model")
    fw.add_argument("--controller-address", default=None,
                    help="FastChat controller URL, e.g. http://host:21001 "
                         "(omit to run unregistered)")
    fw.add_argument("--worker-address", default=None,
                    help="URL the controller should reach us at")
    fw.add_argument("--model-names", default=None,
                    help="comma-separated names to register")
    fw.add_argument("--host", default="127.0.0.1")
    fw.add_argument("--port", type=int, default=21002)
    fw.add_argument("--slots", type=int, default=8)
    fw.add_argument("--max-len", type=int, default=2048)
    fw.add_argument("--paged", action="store_true")
    fw.set_defaults(fn=cmd_fastchat_worker)

    ft = sub.add_parser("fetch-iq-tables",
                        help="download + cache the llama.cpp IQ-quant "
                             "codebook grids (one-time, per machine)")
    # default=None: resolved in cmd_fetch_iq_tables, keeping parser
    # build free of quant imports (file convention)
    ft.add_argument("--url", default=None,
                    help="override the llama.cpp ggml-common.h URL")
    ft.set_defaults(fn=cmd_fetch_iq_tables)

    ti = sub.add_parser("txt2img",
                        help="Stable Diffusion text-to-image (diffusers "
                             "checkpoint dir, fully on-device)",
                        parents=[qp])
    ti.add_argument("model", help="local diffusers pipeline directory")
    ti.add_argument("-p", "--prompt", required=True)
    ti.add_argument("--negative", default=None)
    ti.add_argument("-o", "--output", default="out.png")
    ti.add_argument("--size", type=int, default=512)
    ti.add_argument("--steps", type=int, default=20)
    ti.add_argument("--guidance", type=float, default=7.5)
    ti.add_argument("--seed", type=int, default=0)
    ti.set_defaults(fn=cmd_txt2img)

    ch = sub.add_parser("chat", help="interactive chat REPL", parents=[qp])
    ch.add_argument("model")
    ch.add_argument("-n", "--max-new-tokens", type=int, default=256)
    ch.add_argument("-t", "--temperature", type=float, default=0.7)
    ch.add_argument("--system", default=None, help="system prompt")
    ch.add_argument("--max-len", type=int, default=2048,
                   help="session KV cache length")
    ch.add_argument("--streaming-window", type=int, default=None,
                   help="attention-sink window: unbounded conversation "
                        "in constant memory")
    ch.add_argument("--streaming-sink", type=int, default=4)
    ch.add_argument("--adapter", default=None,
                    help="LoRA adapter artifact (.npz) merged into the "
                         "model for this chat session")
    ch.set_defaults(fn=cmd_chat)

    v = sub.add_parser(
        "verify",
        help="full integrity + numerical validation of a low-bit or "
             "train checkpoint; exit 1 on any finding",
    )
    v.add_argument("path", help="save_low_bit dir, train .npz, or a "
                                "rotation dir of ckpt-*.npz")
    v.set_defaults(fn=cmd_verify)

    ts = sub.add_parser(
        "train-status",
        help="training-run health: last-good step, checkpoint rotation "
             "inventory, supervisor event-log tail (exit 1 when no "
             "checkpoint is loadable)",
    )
    ts.add_argument("ckpt_dir", help="the trainer's --ckpt-dir")
    ts.add_argument("--events", type=int, default=15,
                    help="event-log tail length")
    ts.set_defaults(fn=cmd_train_status)

    tr = sub.add_parser(
        "trace",
        help="serving observability: dump a live server's span ring "
             "buffer (Perfetto-loadable), summarize a trace file into "
             "a latency table, or start/stop a jax.profiler window",
    )
    tr.add_argument("action",
                    choices=("dump", "summarize", "profile-start",
                             "profile-stop"))
    tr.add_argument("target",
                    help="server base URL (dump/profile-*) or a dumped "
                         "trace .json file (summarize)")
    tr.add_argument("-o", "--output", default="trace.json",
                    help="dump: output file")
    tr.add_argument("--logdir", default=None,
                    help="profile-start: jax.profiler output directory "
                         "on the SERVER's filesystem")
    tr.set_defaults(fn=cmd_trace)

    ad = sub.add_parser(
        "adapters",
        help="multi-tenant LoRA lifecycle: list/load/unload against a "
             "live server, or inspect a local adapter artifact "
             "(docs/serving.md §7)",
    )
    ad.add_argument("action",
                    choices=("list", "load", "unload", "inspect"))
    ad.add_argument("target",
                    help="server base URL (list/load/unload) or an "
                         "adapter .npz path (inspect)")
    ad.add_argument("name", nargs="?", default=None,
                    help="adapter name (load/unload)")
    ad.add_argument("--path", default=None,
                    help="load: explicit artifact path (default: "
                         "<adapter-dir>/<name>.npz on the server)")
    ad.add_argument("--pin", action="store_true",
                    help="load: exempt from LRU eviction")
    ad.set_defaults(fn=cmd_adapters)

    sv = sub.add_parser(
        "simserve",
        help="simulated-clock serving benchmark: real engine + virtual "
             "clock + roofline cost model, zero devices (one JSON "
             "report line; docs/benchmarking.md)",
    )
    sv.add_argument("--trace", default="poisson",
                    # literal: keep CLI startup free of sim/jax imports
                    # (must mirror sim/traces.TRACE_NAMES)
                    choices=("poisson", "bursty", "prefix-heavy",
                             "overload", "adapter-zipf", "speculative",
                             "adapter-spec"),
                    help="named trace mix (overload exercises "
                         "preemption AND shed; adapter-zipf the "
                         "multi-tenant LoRA registry churn; adapter-spec "
                         "adapters THROUGH speculative decode under a "
                         "tight unified page pool)")
    sv.add_argument("--speculative", action="store_true",
                    help="run the mix through draft+verify speculative "
                         "rounds regardless of its scenario default "
                         "(adapter mixes verify with the adapter "
                         "applied)")
    sv.add_argument("--draft-k", type=int, default=None,
                    help="draft length for --speculative (implies it "
                         "when set; default: the scenario's draft_k)")
    sv.add_argument("--trace-file", default=None,
                    help="replay a banked trace JSONL instead of "
                         "generating one")
    sv.add_argument("--seed", type=int, default=0,
                    help="trace-generator seed (same seed = "
                         "byte-identical trace and report)")
    sv.add_argument("--hbm-gbps", type=float, default=None,
                    help="cost-model calibration knob: achievable HBM "
                         "GB/s of the modeled chip (default v5e-class)")
    sv.add_argument("--ici-gbps", type=float, default=None,
                    help="cost-model calibration knob: per-link ICI "
                         "GB/s for the modeled TP ring (default "
                         "v5e-class; only matters with --tp > 1)")
    sv.add_argument("--tp", type=int, default=None,
                    help="model the per-layer TP all-reduce for this "
                         "ring size (additive comm overhead; "
                         "default 1 = no collective term)")
    sv.add_argument("--comm-qtype", default=None,
                    choices=("none", "int8", "fp8_e4m3"),
                    help="price the modeled all-reduce at this "
                         "block-scaled wire format instead of fp32 "
                         "(benchmark/roofline.all_reduce_cost)")
    sv.add_argument("--save-trace", default=None,
                    help="bank the generated arrival trace as crc'd "
                         "JSONL")
    sv.add_argument("-o", "--output", default=None,
                    help="also write the report JSON to a file "
                         "(atomic)")
    sv.set_defaults(fn=cmd_simserve)

    ln = sub.add_parser(
        "lint",
        help="graftlint: AST invariant checks over bigdl_tpu/ (clock "
             "injection, atomic writes, fault points, lock discipline, "
             "metrics drift, donation, journal crc, plus the v2 "
             "interprocedural families: PAGE page-leak proofs, LCK "
             "lock-order cycles, DSP dispatch consistency; exit 1 on "
             "any non-baselined finding — docs/static-analysis.md)",
    )
    ln.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the installed "
                         "bigdl_tpu package)")
    ln.add_argument("--baseline", default=None,
                    help="baseline JSON (default: the checked-in "
                         "bigdl_tpu/analysis/baseline.json)")
    ln.add_argument("--rules", default=None,
                    help="comma-separated rule subset, e.g. WCT001,ATW001")
    ln.add_argument("--write-baseline", action="store_true",
                    help="record current findings as the new baseline "
                         "(each entry then needs a justification edit)")
    ln.add_argument("--update-baseline", action="store_true",
                    help="regenerate the baseline in place: stale "
                         "entries drop, surviving justifications carry "
                         "over")
    ln.add_argument("--format", choices=("human", "json", "github"),
                    default="human",
                    help="output format (github = ::error annotation "
                         "lines for CI inline comments)")
    ln.add_argument("--list-rules", action="store_true")
    ln.set_defaults(fn=cmd_lint)

    b = sub.add_parser("bench", help="quick decode-latency check", parents=[qp])
    b.add_argument("model")
    def _min2(v):
        iv = int(v)
        if iv < 2:  # one timed token can't separate decode from first-token
            raise argparse.ArgumentTypeError("--out-len must be >= 2")
        return iv

    b.add_argument("--in-len", type=int, default=32)
    b.add_argument("--out-len", type=_min2, default=32)
    b.set_defaults(fn=cmd_bench)

    args = p.parse_args(argv)
    # the lint gate never imports jax; the simulator pins itself to the
    # CPU, where the cache stays off
    if args.fn not in (cmd_lint, cmd_simserve):
        from bigdl_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
