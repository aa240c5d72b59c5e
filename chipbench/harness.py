"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:

* ``chipbench/configs/<config>.json``: the configuration's sizes, the
  repo's id for it and the engine settings;
* ``chipbench/traffic/<mix>.json``: the traffic parameters
  (``chipbench.traffic`` reads them);
* ``chipbench/metrics/<metric>.py``: a reader with ``read(run)`` that
  returns the metric's value from the trace and counters, or ``None``;
* ``chipbench/reference/<family>.py``: the plain reference of a family.

The system under test is ``ServingEngine`` (``src/repro/serving``): the
window calls ``submit`` and loops on ``step`` in this process.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from chipbench import check, flops
from chipbench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"
TRACE_AT = 0.3  # the traced slice starts this far into the window
# it lasts this long, and on until it holds an admission and two decode
# steps, so that every per-layer metric finds something to read
TRACE_SLICE_S = 3.0

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """The machine lacks what the cell needs: no run, no result."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config_file(name: str) -> Path:
    return HERE / "configs" / f"{name}.json"


def mix_file(name: str) -> Path:
    return HERE / "traffic" / f"{name}.json"


def reader_file(metric: str) -> Path:
    return HERE / "metrics" / f"{metric}.py"


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The ``read`` function of a per-layer metric."""
    return load_module(reader_file(metric), f"chipbench_metric_{metric}").read


def load_reference(family: str):
    """The plain reference class of a model family."""
    mod = load_module(HERE / "reference" / f"{family}.py",
                      f"chipbench_reference_{family}")
    return getattr(mod, f"{family.capitalize()}Reference")


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]


def _for_cell(metrics: list[dict], cell: str) -> list[dict]:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    return Cell(name, w["config"], load_json(config_file(w["config"])),
                w["traffic"], load_json(mix_file(w["traffic"])), int(w["chips"]),
                _for_cell(bench["end_to_end"], name),
                _for_cell(bench["per_layer"], name))


def load_peaks(kind: str) -> dict:
    peaks = load_json(HERE / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json")
    return peaks[kind]


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    pos = q / 100.0 * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


class CompileCounter:
    """Counts the executables JAX compiles, and those it loads from the
    persistent cache, while active."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: the repo's
    entry, run with the file's rotary base and norm epsilon, and checked
    against the file's widths."""
    from repro.configs import get

    mc = dataclasses.replace(get(cfg["repo_config"]),
                             rope_theta=float(cfg["rope_theta"]),
                             norm_eps=float(cfg["rms_norm_eps"]),
                             remat="none")
    want = {"n_layers": cfg["num_hidden_layers"], "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
            "head_dim_": cfg["head_dim"], "family": cfg["family"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "qkv_bias": cfg["qkv_bias"]}
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"program config {cfg['repo_config']} {got} "
                         f"differs from the file's {want}")
    return mc


# ---------------------------------------------------------------- the run


@dataclass
class Record:
    """What the client saw of one request."""

    draw: object
    req: object
    due: float
    submitted: float
    token_times: list[float] = field(default_factory=list)
    done_at: float | None = None


@dataclass
class Counters:
    """Counts the benchmark's wrappers take while the profiler runs."""

    admitted_prompt_tokens: int = 0
    prefill_tokens: int = 0
    prefill_flops: int = 0
    decode_launches: int = 0
    decode_flops: int = 0


class Run:
    """One run of a cell. ``model_cfg`` lets a test put a small program
    configuration in the file's place; ``engine_hook`` lets a test break
    the timed path underneath (it gets the engine after warm-up)."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 *, model_cfg=None, engine_hook=None):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.model_cfg = model_cfg or program_config(cell.config)
        self.engine_hook = engine_hook
        self.limit = cell.config["correct"]["max_logit_gap"]
        self.counters = Counters()
        self.records: list[Record] = []
        self.trace_data = None
        self.trace_pause_s = 0.0
        self.probes: dict[int, list] = {}  # id(request) -> (pos, slot, probe)

    # ------------------------------------------------------------ set-up

    def setup(self) -> None:
        import jax

        from chipbench.weights import make_params
        from repro.models.model import Model
        from repro.serving import Request, ServingEngine

        eng = self.cell.config["engine"]
        slots = self.cell.mix["engine"]["slots"]
        max_len = self.cell.mix["engine"]["max_len"]
        model = Model(self.model_cfg)
        self.params = make_params(
            jax.eval_shape(model.init, jax.random.key(0)), self.seed)
        self.engine = ServingEngine(
            model, self.params, max_slots=slots, max_len=max_len,
            sampling=eng["sampling"], sample_backend=eng["sample_backend"],
            prefill_chunk=eng["prefill_chunk"])
        # warm-up: one prefill chunk and one decode step at the cell's shapes
        chunk = eng["prefill_chunk"]
        self.engine.submit(Request(uid=-1, prompt=list(range(1, chunk + 2)),
                                   max_new_tokens=1))
        self.engine.run_until_done()
        self.engine.finished.clear()
        jax.block_until_ready(self.engine.cache)
        if self.engine_hook is not None:
            self.engine_hook(self.engine)

    # ------------------------------------------------------------ window

    def _wrap(self):
        """Host spans and counters around the engine's layers; returns the
        function that takes them off again."""
        import jax

        eng, c, cfg = self.engine, self.counters, self.cell.config
        admit, prefill, decode = eng._admit, eng._prefill, eng._decode

        def admit_w():
            before = {id(r) for r in eng.slot_req if r is not None}
            with jax.profiler.TraceAnnotation("chipbench.admit"):
                admit()
            for r in eng.slot_req:
                if r is not None and id(r) not in before:
                    n = len(r.prompt)
                    c.admitted_prompt_tokens += n
                    c.prefill_tokens += n - 1
                    c.prefill_flops += flops.prefill_flops(cfg, n)

        def prefill_w(*args):
            with jax.profiler.TraceAnnotation("chipbench.prefill_launch"):
                return prefill(*args)

        def decode_w(*args):
            live = eng.live_slots
            c.decode_launches += 1
            c.decode_flops += flops.decode_flops(
                cfg, [int(eng.positions[s]) + 1 for s in live])
            with jax.profiler.TraceAnnotation("chipbench.decode_launch"):
                return decode(*args)

        eng._admit, eng._prefill, eng._decode = admit_w, prefill_w, decode_w

        def unwrap():
            del eng._admit  # the instance attribute hid the method
            eng._prefill, eng._decode = prefill, decode

        return unwrap

    def _keep_probes(self):
        """Keep, for the check, the first token of the last position of
        each prefill chunk, as the chunk's program returns it (a device
        array, read once the window has closed); returns the function that
        stops keeping them."""
        eng, probes = self.engine, self.probes
        launch = eng._launch

        def launch_w(desc):
            out = launch(desc)
            if "prefill_tokens" in desc:
                slot = int(np.argmax(desc["slot_mask"]))
                pos = int(desc["positions"][slot]) + int(desc["prefill_len"]) - 1
                probes.setdefault(id(eng.slot_req[slot]), []).append((pos, slot, out))
            return out

        eng._launch = launch_w

        def unwrap():
            del eng._launch  # the instance attribute hid the method

        return unwrap

    def window(self) -> None:
        import jax

        from repro.serving import Request

        eng, traffic = self.engine, Traffic(self.cell.mix, self.seed,
                                            self.seconds, self.model_cfg.vocab_size)
        active: list[Record] = []

        def submit(draw, due, now):
            req = Request(uid=draw.index, prompt=draw.prompt,
                          max_new_tokens=draw.max_new_tokens)
            eng.submit(req)
            rec = Record(draw, req, due, now)
            self.records.append(rec)
            active.append(rec)

        schedule = traffic.open_schedule() if traffic.loop == "open" else []
        nxt = 0
        trace_state = "before" if self.trace else "off"
        unwrap = slice_span = None
        t0 = time.perf_counter()
        end = t0 + self.seconds
        stop_probes = self._keep_probes()
        if traffic.loop == "closed":
            for _ in range(traffic.clients):
                submit(traffic.next_draw(), t0, t0)
        with CompileCounter() as counter:
            while True:
                now = time.perf_counter()
                if trace_state == "before" and now >= t0 + TRACE_AT * self.seconds:
                    shutil.rmtree(TRACE_DIR, ignore_errors=True)
                    jax.profiler.start_trace(str(TRACE_DIR))
                    unwrap = self._wrap()
                    slice_span = jax.profiler.TraceAnnotation("chipbench.slice")
                    slice_span.__enter__()
                    # starting and stopping the profiler is not serving: the
                    # window's clock stands still for both
                    paused = time.perf_counter() - now
                    t0, end, now = t0 + paused, end + paused, now + paused
                    self.trace_pause_s += paused
                    trace_state, slice_end = "on", now + TRACE_SLICE_S
                elif trace_state == "on" and (now >= end or (
                        now >= slice_end and self.counters.admitted_prompt_tokens
                        and self.counters.decode_launches >= 2)):
                    slice_span.__exit__(None, None, None)
                    unwrap()
                    jax.profiler.stop_trace()
                    trace_state = "done"
                    paused = time.perf_counter() - now
                    t0, end, now = t0 + paused, end + paused, now + paused
                    self.trace_pause_s += paused
                if now >= end:
                    break
                while nxt < len(schedule) and t0 + schedule[nxt].due_s <= now:
                    submit(schedule[nxt], t0 + schedule[nxt].due_s, now)
                    nxt += 1
                if not eng.queue and not eng.live_slots:
                    wake = min(t0 + schedule[nxt].due_s if nxt < len(schedule)
                               else end, end)
                    if trace_state == "on":
                        with jax.profiler.TraceAnnotation("chipbench.wait"):
                            time.sleep(max(0.0, wake - now))
                    else:
                        time.sleep(max(0.0, wake - now))
                    continue
                if trace_state == "on":
                    with jax.profiler.TraceAnnotation("chipbench.step"):
                        eng.step()
                else:
                    eng.step()
                t = time.perf_counter()
                keep, turns = [], 0
                for rec in active:
                    n = len(rec.req.generated)
                    if n > len(rec.token_times):
                        rec.token_times += [t] * (n - len(rec.token_times))
                    if rec.req.done:
                        rec.done_at = t
                        turns += 1
                    else:
                        keep.append(rec)
                active[:] = keep
                if traffic.loop == "closed":
                    for _ in range(turns):  # each client sends its next turn
                        submit(traffic.next_draw(), t, t)
        self.window_s = time.perf_counter() - t0
        stop_probes()
        self.t0, self.t_end = t0, t0 + self.window_s
        self.window_compiles = counter.compiles + counter.cache_hits
        self.late_s = [r.submitted - r.due for r in self.records]

    # ----------------------------------------------------------- metrics

    def client_values(self) -> dict:
        """What the client saw over the window, by metric name."""
        due = [r for r in self.records if r.due <= self.t_end]
        ttft = [((r.token_times[0] if r.token_times else self.t_end) - r.due) * 1e3
                for r in due]
        gaps = [(b - a) * 1e3 for r in self.records
                for a, b in zip(r.token_times, r.token_times[1:])]
        tokens = sum(len(r.token_times) for r in self.records)
        return {"output_tok_s": tokens / self.window_s,
                "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
                "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
                "itl_p99_ms": percentile(gaps, 99) if gaps else None,
                "setup_s": self.setup_s}

    def end_to_end(self) -> dict:
        values = self.client_values()
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in self.cell.end_to_end if values.get(m["name"]) is not None}

    def per_layer(self, peaks: dict) -> dict:
        out = {}
        for m in self.cell.per_layer:
            value = load_reader(m["name"])(self, peaks)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    # ------------------------------------------------------------- check

    def finished(self) -> list[check.Served]:
        out = []
        for r in self.records:
            if r.done_at is None or r.done_at > self.t_end:
                continue
            probes = self.probes.get(id(r.req), [])
            out.append(check.Served(
                list(r.req.prompt), list(r.req.generated),
                [(pos, int(np.asarray(ids)[slot, 0])) for pos, slot, ids in probes],
                probes[0][1] if probes else None))
        return out

    def release_program(self) -> None:
        """Drop the engine and its cache; the weights stay for the check."""
        self.engine = None
        gc.collect()

    def compare(self, control: bool = False) -> dict:
        cls = load_reference(self.cell.config["family"])
        ref = cls(self.cell.config, self.params)
        ctl = cls(self.cell.config, self.params, precision="fp8") if control else None
        return check.widest_gap(ref, check.sample(self.finished(), self.seed), ctl)


def device_info(chips: int) -> tuple[object, dict]:
    """The first chip and its peaks; raises ``NoChip`` where the machine
    has no TPU, too few chips, or a kind the peaks table lacks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[0], load_peaks(devs[0].device_kind)


def set_compile_cache() -> None:
    """JAX's persistent compilation cache, in the checkout at a fixed path
    and for every program. Entry points call it before JAX first touches
    the chip; tests never do, so that they write nothing to the checkout."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # no eviction, whatever the environment asks: the cell's dozen programs
    # are a few hundred MB, and an evicted entry compiles in the next run
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, *, require_chip: bool = True, **run_kw) -> dict:
    """One whole run; returns the result object. ``require_chip=False``
    (tests only) skips the look for a chip."""
    import jax

    if require_chip:
        dev, peaks = device_info(cell.chips)
    else:
        dev, peaks = jax.devices()[0], load_json(HERE / "peaks.json")["TPU v5 lite"]
    run = Run(cell, seed, seconds, trace, **run_kw)
    with CompileCounter() as setup_counter:
        run.setup()
    run.setup_s = time.perf_counter() - t_start
    run.window()
    late = sorted(run.late_s) or [0.0]
    print(f"window: {run.window_s:.3f} s, {len(run.records)} requests sent, "
          f"generator late by median {percentile(late, 50) * 1e3:.3f} ms, "
          f"max {late[-1] * 1e3:.3f} ms; profiler start and stop "
          f"{run.trace_pause_s:.3f} s, outside the window; set-up {run.setup_s:.3f} s with "
          f"{setup_counter.compiles} compiles and {setup_counter.cache_hits} "
          "persistent-cache hits", file=sys.stderr, flush=True)
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    if trace:
        from chipbench.trace import Trace

        run.trace_data = Trace.from_dir(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        metrics = run.per_layer(peaks)
        device["busy_s"] = run.trace_data.busy_ns() * 1e-9
        device["window_s"] = run.trace_data.window_ns * 1e-9
    else:
        metrics = run.end_to_end()
    run.release_program()
    found = run.compare()
    correct, checks = decide(found["max_logit_gap"], run.limit,
                             run.window_compiles, found["served_tokens"])
    result = {"correct": correct,
              "attempted": sum(r.due <= run.t_end for r in run.records),
              "failed": 0, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = run.trace_data.breakdown()
    result["checks"] = checks
    print(f"check: {found['requests']} requests from {found['slots']} slots, "
          f"{found['served_tokens']} served tokens and "
          f"{found['prefill_positions']} prefill chunks' last positions "
          "compared with the float32 reference", file=sys.stderr)
    for name, c in checks.items():
        rel = ">=" if name == "checked_tokens" else "<="
        print(f"check: {name} {c['value']} (limit {rel} {c['limit']})",
              file=sys.stderr)
    return result


def decide(max_logit_gap: float, limit, window_compiles: int,
           served_tokens: int) -> tuple[bool, dict]:
    """``correct`` and the numbers it was decided on, each beside its
    limit: the widest logit gap, the compiles inside the window, and the
    served tokens checked."""
    checks = {
        "max_logit_gap": {"value": max_logit_gap, "limit": limit},
        "window_compiles": {"value": window_compiles, "limit": 0},
        "checked_tokens": {"value": served_tokens, "limit": 1},
    }
    correct = (limit is not None and max_logit_gap <= limit
               and window_compiles == 0 and served_tokens >= 1)
    return correct, checks
