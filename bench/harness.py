"""One run of one cell: set-up, warm-up, the measured window, the traced
reading of per-layer metrics, and the check against the reference.

The harness is the client. It makes the requests from the cell's traffic
mix, submits each when it is due, drives the engine with ``step()`` and
watches every request's tokens arrive on the host. Everything it needs to
know about a cell comes from files found by name: the configuration
(``configs/<config>.json``), the mix (``traffic/<mix>.json``, read by the
generator the mix names), the limits of the check (``limits/<cell>.json``)
and one reader per per-layer metric (``metrics/<metric>.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 10.0  # a traced run traces this much of its window, from the start
WARMUP_LIMIT_S = 300.0  # a backlog's first wave must be decoding by then


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ files


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    conf: dict  # configs/<config>.json
    mix: dict  # traffic/<mix>.json
    limits: dict  # limits/<cell>.json
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(root / confs[w["config"]]["file"])

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, conf=conf,
        mix=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(HERE / "limits" / f"{name}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


def metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------- process


def process_start() -> float:
    """When this process started, on the ``time.time()`` clock."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, so that
    only a checkout's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def pin_block_sizes() -> None:
    """Kernel block sizes from the tuner's defaults, so that no file git
    does not track can move the yardstick."""
    from repro.kernels import autotune

    autotune.set_tuner(autotune.Autotuner(path=os.devnull, sweep=False))


class CompileMeter:
    """Counts XLA backend compiles while installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


# ------------------------------------------------------------------ model

# published key -> how the program's ArchConfig states it
_ARCH_KEYS = {
    "hidden_size": lambda c: c.d_model,
    "num_hidden_layers": lambda c: c.n_layers,
    "num_attention_heads": lambda c: c.n_heads,
    "num_key_value_heads": lambda c: c.n_kv_heads,
    "intermediate_size": lambda c: c.dense_ff or c.d_ff,  # the dense MLP's width
    "vocab_size": lambda c: c.vocab_size,
    "tie_word_embeddings": lambda c: c.tie_embeddings,
    "rope_theta": lambda c: c.rope_theta,
    "rms_norm_eps": lambda c: c.norm_eps,
    "q_lora_rank": lambda c: c.mla.q_lora_rank,
    "kv_lora_rank": lambda c: c.mla.kv_lora_rank,
    "qk_nope_head_dim": lambda c: c.mla.nope_head_dim,
    "qk_rope_head_dim": lambda c: c.mla.rope_head_dim,
    "v_head_dim": lambda c: c.mla.v_head_dim,
    "moe_intermediate_size": lambda c: c.moe.expert_ff or c.d_ff,
    "num_experts_per_tok": lambda c: c.moe.top_k,
    "n_shared_experts": lambda c: c.moe.n_shared,
    "first_k_dense_replace": lambda c: c.first_k_dense,
}


def as_run(conf: dict) -> dict:
    """The configuration as the program runs it: the published keys, with
    the program's departures (``runs_as``, each explained under
    ``assumed``) in their place. An expert model's ``n_routed_experts``
    counts the experts this chip holds; ``deployment.experts``
    (``published``, ``shards``, ``first``) states the published count and
    the first expert held, handed on as ``n_routed_experts_published`` and
    ``first_routed_expert`` (all of them and 0 where one chip holds all)."""
    hf = {**conf["config"], **conf.get("runs_as", {})}
    if "n_routed_experts" in hf:
        experts = conf.get("deployment", {}).get("experts", {})
        hf["n_routed_experts_published"] = experts.get("published", hf["n_routed_experts"])
        hf["first_routed_expert"] = experts.get("first", 0)
    return hf


def arch_config(conf: dict):
    """The program's configuration for ``conf``: its registry arch cut to
    the file's depth, checked key by key against the file as run."""
    from repro.configs import get_arch

    hf = as_run(conf)
    cfg = dataclasses.replace(
        get_arch(conf["arch"]), n_layers=hf["num_hidden_layers"],
        dtype=conf["deployment"]["dtype"],
    )
    for key, get in _ARCH_KEYS.items():
        if key not in hf:
            continue
        # a published null query LoRA is the program's 0: a dense projection
        want = 0 if hf[key] is None and key == "q_lora_rank" else hf[key]
        if get(cfg) != want:
            raise ValueError(f"{conf['name']}: program has {key}={get(cfg)}, file {hf[key]}")
    if "n_routed_experts" in hf:
        held, published = hf["n_routed_experts"], hf["n_routed_experts_published"]
        experts = conf["deployment"].get("experts")
        if experts and (published != held * experts["shards"] or hf["first_routed_expert"] % held
                        or not 0 <= hf["first_routed_expert"] < published):
            raise ValueError(f"{conf['name']}: deployment.experts {experts} does not cut "
                             f"{published} experts into blocks of {held}")
        from repro.models import LM

        # experts held and routed over, as the program's parameter shapes
        # state them: the expert stack's expert axis, the router's width
        moe = LM(cfg).param_specs()["moe_blocks"]["moe"]
        got = (moe["w_in"].shape[1], moe["router"].shape[-1])
        if got != (held, published):
            raise ValueError(f"{conf['name']}: program holds {got[0]} of {got[1]} experts, "
                             f"file {held} of {published}")
    return cfg


# ----------------------------------------------------------------- client


@dataclass
class Watch:
    """What the client sees of one request."""

    spec: object  # generators' Spec
    due: float  # scheduled time on the host clock
    req: object = None
    seen: int = 0
    first_at: float | None = None
    win_first: float | None = None  # first observation inside the window
    win_last: float | None = None
    win_after_first: int = 0  # tokens observed after win_first
    win_tokens: int = 0  # every token observed inside the window
    done_at: float | None = None


@dataclass
class StepRecord:
    """One ``step()`` of the traced window, for the per-layer readers."""

    t: float
    waiting: int
    busy: int
    pack: list = field(default_factory=list)  # [(start, stop)] prompt positions
    admits: list = field(default_factory=list)  # [(0, s)] fused admissions
    decode: list = field(default_factory=list)  # per decode step: [contexts]


@dataclass
class Run:
    """What a run hands to the per-layer metric readers."""

    hf: dict
    peak: dict
    chips: int
    slots: int
    window_s: float
    steps: list
    trace: object  # trace.Trace
    devices: list


class Client:
    """Submits each request when due and watches its tokens arrive. Due
    times count from ``base``; tokens count for the window only between
    ``open_window`` and ``t_close``."""

    def __init__(self, engine, specs, base: float):
        from repro.serve import Request, SamplingParams

        self.engine = engine
        self.t_open = self.t_close = math.inf
        self.pending = sorted((Watch(s, base + s.due_s) for s in specs), key=lambda w: w.due)
        self.all = list(self.pending)
        self.live: list[Watch] = []
        self.record = False
        self.steps: list[StepRecord] = []
        self._mk = lambda s: Request(
            rid=s.rid, prompt=s.prompt, params=SamplingParams(max_new=s.max_new)
        )

    def open_window(self, t_open: float, seconds: float, record: bool) -> None:
        self.t_open, self.t_close, self.record = t_open, t_open + seconds, record

    def submit_due(self, now: float) -> None:
        import jax

        while self.pending and self.pending[0].due <= now:
            w = self.pending.pop(0)
            with jax.profiler.TraceAnnotation("bench.submit"):
                w.req = self._mk(w.spec)
                self.engine.submit(w.req)
            self.live.append(w)

    def observe(self, now: float) -> None:
        for w in self.live:
            n = len(w.req.generated)
            if n > w.seen:
                if w.first_at is None:
                    w.first_at = now
                if self.t_open <= now < self.t_close:
                    w.win_tokens += n - w.seen
                    if w.win_first is None:
                        w.win_first = now
                    else:
                        w.win_after_first += n - w.seen
                        w.win_last = now
                w.seen = n
            if w.req.complete and w.done_at is None:
                w.done_at = now
        self.live = [w for w in self.live if w.done_at is None]

    def step(self) -> bool:
        import jax

        eng = self.engine
        if self.record:
            before = [(r, int(eng.slot_fed[i]), r.n_generated if r else 0)
                      for i, r in enumerate(eng.slot_req)]
            ticks0 = eng.stream_stats.ticks
        with jax.profiler.TraceAnnotation("bench.step"):
            busy = eng.step()
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.observe"):
            self.observe(now)
        if self.record:
            self._record(now, before, eng.stream_stats.ticks - ticks0)
        return busy

    def _record(self, now, before, dticks) -> None:
        """The work this step dispatched, from the engine's slot table and
        tick counter: prompt positions packed or admitted, and the context
        of every active slot at each decode step."""
        eng = self.engine
        rec = StepRecord(now, len(eng.waiting), sum(r is not None for r in eng.slot_req))
        decoders = []

        def progress(r, fed0, n0, fed1):
            s = len(r.prompt)
            if fed1 > fed0:
                if s <= eng.prefill_budget:
                    rec.admits.append((0, s))
                else:
                    rec.pack.append((fed0, fed1))
            first = 1 if n0 == 0 and r.n_generated > 0 else 0
            if r.n_generated - n0 - first > 0:
                decoders.append(s + max(n0, 1))

        for i, (r0, fed0, n0) in enumerate(before):
            r1 = eng.slot_req[i]
            if r0 is not None:
                progress(r0, fed0, n0, int(eng.slot_fed[i]) if r1 is r0 else len(r0.prompt))
            if r1 is not None and r1 is not r0:
                progress(r1, 0, 0, int(eng.slot_fed[i]))
        k = dticks - (1 if rec.pack else 0)
        rec.decode = [[c + j for c in decoders] for j in range(k)] if decoders else []
        self.steps.append(rec)

    def run_until(self, t_end: float, cond=None) -> None:
        """Submit and step until ``t_end`` (or until ``cond()`` holds)."""
        while True:
            now = time.perf_counter()
            if now >= t_end or (cond is not None and cond()):
                return
            self.submit_due(now)
            if not self.step() and self.pending:
                wait = min(self.pending[0].due, t_end) - time.perf_counter()
                if wait > 0:
                    time.sleep(min(wait, 1e-3))


# -------------------------------------------------------------------- run


def _percentile(xs: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else float("nan")


def end_to_end(client: Client, seconds: float) -> dict:
    """The window's end-to-end numbers from what the client saw: TTFT of
    every request due in the window (one with no first token by the close
    counts at its age then), time per output token between a request's
    first and last token observations in the window, and output tokens
    observed in the window per second."""
    t_open, t_close = client.t_open, client.t_close
    due = [w for w in client.all if t_open <= w.due < t_close]
    late = [w for w in due if w.first_at is None or w.first_at >= t_close]
    ttft = [((w.first_at if w not in late else t_close) - w.due) * 1e3 for w in due]
    tpot = [(w.win_last - w.win_first) / w.win_after_first * 1e3
            for w in client.all if w.win_after_first > 0]
    tokens = sum(w.win_tokens for w in client.all)
    return {
        "ttft_p90_ms": _percentile(ttft, 90),
        "tpot_p90_ms": _percentile(tpot, 90),
        "output_tok_s": tokens / seconds,
        "due": len(due),
        "no_first_token": len(late),
        "tpot_samples": len(tpot),
        "waiting_at_close": len(client.engine.waiting),
    }


def build_engine(cell: Cell, seed: int, devices):
    """Weights from the seed on the device, then the engine of the cell's
    deployment (one chip, or the merge cluster's tensor-parallel engine)."""
    import jax

    from bench import weights
    from repro.models import LM
    from repro.serve import ServeEngine

    dep = cell.conf["deployment"]
    cfg = arch_config(cell.conf)
    kw = dict(batch_slots=dep["slots"], max_len=dep["max_len"], seed=seed % (1 << 31))
    if dep["mode"] == "merge":
        from repro.dist.sharding import param_shardings, serving_mesh_info
        from repro.serve import ServeCluster

        model = LM(cfg)
        specs = model.param_specs()
        info = serving_mesh_info(devices)
        params = weights.build(specs, seed, out_shardings=param_shardings(specs, info))
        jax.block_until_ready(params)
        cluster = ServeCluster(model, params, mode="merge", devices=devices, **kw)
        return cluster.engines[0], cluster
    model = LM(cfg)
    params = jax.block_until_ready(weights.build(model.param_specs(), seed))
    return ServeEngine(model, params, **kw), None


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, control: bool = False, t_proc: float | None = None,
        log=print) -> dict:
    """One run; returns the result line's object (``correct`` and the
    numbers compared, the metrics, the device). With ``control`` the check
    holds the control's tokens, not the program's, against the limit
    (``bench/control.py``; never in a benchmark run)."""
    t_proc = process_start() if t_proc is None else t_proc
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    devs = devs[: cell.chips]
    cache_dir = enable_compile_cache() if require_chip else "off"
    pin_block_sizes()
    meter = CompileMeter()
    gen = importlib.import_module(f"bench.generators.{cell.mix['generator']}")
    hf, dep = as_run(cell.conf), cell.conf["deployment"]
    engine, cluster = build_engine(cell, seed, devs)
    engine.prewarm()
    specs = gen.generate(cell.mix, seed, slots=dep["slots"], seconds=seconds,
                         vocab=hf["vocab_size"], max_len=dep["max_len"])
    # set-up: warm-up traffic up to the window's opening
    t_warm = time.perf_counter()
    if cell.mix["arrivals"]["kind"] == "backlog":
        # the backlog is due at warm-up's start; the window opens once every
        # slot's first occupant has its first token
        client = Client(engine, specs, t_warm + cell.mix["warmup_s"])
        first = [w for w in client.all if w.spec.first_wave]
        client.run_until(t_warm + WARMUP_LIMIT_S, cond=lambda: all(w.seen > 0 for w in first))
        t_open = time.perf_counter()
        if not all(w.seen > 0 for w in first):
            raise RuntimeError(f"warm-up: the first wave had no first tokens after "
                               f"{WARMUP_LIMIT_S} s")
    else:
        t_open = t_warm + cell.mix["warmup_s"]
        client = Client(engine, specs, t_open)
        client.run_until(t_open)
    compiles0 = meter.n
    setup_s = time.time() - t_proc
    log(f"set-up {setup_s:.3f} s (compile cache {cache_dir}); window opens")

    # the measured window
    client.open_window(t_open, seconds, record=trace)
    if trace:
        from bench import trace as trace_mod

        jax.block_until_ready(engine.cache)
        with trace_mod.capture(str(TRACE_DIR)):
            client.run_until(t_open + min(seconds, TRACE_SECONDS))
            jax.block_until_ready(engine.cache)
        traced_to = time.perf_counter()
        client.record = False
    client.run_until(client.t_close)
    compiles = meter.n - compiles0
    client.observe(time.perf_counter())  # completions of the last step
    log(f"window closed: {compiles} compiles inside it")

    values = end_to_end(client, seconds)
    values["setup_s"] = setup_s
    submitted = [w for w in client.all if w.req is not None]
    finished = [w for w in submitted if w.done_at is not None]
    log(f"submitted {len(submitted)}, finished {len(finished)}; {values}")

    stats = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}

    per_layer = {}
    breakdown = None
    if trace:
        from bench import trace as trace_mod

        t = trace_mod.load(str(TRACE_DIR))
        ids = sorted(t.ops)[: cell.chips]
        peaks = load_json(HERE / "peaks.json")["devices"]
        if devs[0].device_kind not in peaks and require_chip:
            raise KeyError(f"device kind {devs[0].device_kind!r} is not in peaks.json")
        peak = peaks.get(devs[0].device_kind) or next(iter(peaks.values()))
        steps = [s for s in client.steps if t_open <= s.t <= traced_to]
        rn = Run(hf=hf, peak=peak, chips=cell.chips, slots=dep["slots"],
                 window_s=t.window, steps=steps,
                 trace=t, devices=ids)
        for m in cell.per_layer if ids else []:
            v = metric_reader(m["name"])(rn)
            if v is not None:
                per_layer[m["name"]] = v
        device["busy_s"] = trace_mod.busy_seconds(t, ids)
        device["window_s"] = t.window
        if ids:
            breakdown = {"device_ops": trace_mod.top_ops(t, ids[0]),
                         "idle_gaps": trace_mod.idle_gaps(t, ids[0])}

    # the check, once the program's state is freed
    served = []
    short = bad = 0
    for w in finished:
        toks = np.asarray(w.req.generated, np.int64)
        if w.req.finish_reason != "length" or len(toks) != w.spec.max_new:
            short += 1
        if toks.size and (toks.min() < 0 or toks.max() >= hf["vocab_size"]):
            bad += 1
        served.append((w.spec.prompt, toks.astype(np.int32)))
    del engine, cluster, client
    gc.collect()
    from bench import check

    sample = check.choose([s for s in served if len(s[1])], seed) if not bad else []
    t_ref = time.perf_counter()
    g = check.gaps(cell.conf, seed, sample, control=control) if sample else {
        "max_gap": None, "tokens": 0, "requests": 0, "differing": 0}
    g["seconds"] = time.perf_counter() - t_ref
    lim = cell.limits
    gap = g.get("control_gap") if control else g["max_gap"]
    checks = {
        "max_gap": {"value": gap, "limit": lim["max_gap"]},
        "tokens_compared": {"value": g["tokens"], "limit": lim["min_tokens"]},
        "short_requests": {"value": short, "limit": 0},
        "out_of_vocab": {"value": bad, "limit": 0},
    }
    correct = (
        gap is not None and gap <= lim["max_gap"]
        and g["tokens"] >= lim["min_tokens"]
        and short == 0 and bad == 0
    )
    log(f"reference: {g}")
    metrics = {}
    if trace:
        for m in cell.per_layer:
            if m["name"] in per_layer:
                metrics[m["name"]] = {"value": per_layer[m["name"]], "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": len(submitted),
        "failed": short + bad,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control"] = {"program_gap": g["max_gap"],
                             "control_differing": g.get("control_differing")}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    import argparse

    t_proc = process_start()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: no program here (src/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        cell = find_cell(args.workload)
        res = run(cell, args.seed, args.seconds, bool(args.trace), t_proc=t_proc, log=log)
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0
