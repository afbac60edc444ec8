"""On-chip benchmark of the low-rank training step: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights on the device from ``--seed``, builds the job's
optimizer (``repro.core.make_optimizer``) and train step
(``repro.train.step.make_train_step``) and starts ``repro.train.loop.
train_loop`` over the benchmark's token feed.  The loop runs the job's
schedule (a refresh step at ``step % tau == 0``, checkpoints off): its
first ``check_steps`` steps, step 0's refresh among them, are set-up and
are what the reference checks.  The same loop, state and executables then
run the measured window: ``--seconds`` of steps, ended in
``block_until_ready``; the feed closes the window by raising out of the
loop.  At most two steps are in flight, so the window overruns its length
by at most two steps' time and all of it counts.

``--trace 1`` traces a window of the workload's ``trace_steps`` steps
instead and reports the per-layer metrics.  After the window the program's
state is freed and the reference runs; ``correct`` is its verdict.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last, ``check``:
each number compared with its limit).  Without a TPU the run exits
non-zero with no result; ``--rehearsal`` runs a tiny size on the CPU and
reports no device metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, NamedTuple, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
GIB = 2.0 ** 30
CACHE_CAP = 2 ** 31  # bytes


def bootstrap() -> str:
    """Put the checkout on the path and place the compile cache as the
    program's entry points do, before JAX is imported; returns the cache
    directory."""
    if not os.path.isdir(os.path.join(CHECKOUT, "src", "repro")):
        raise SystemExit(f"no src/repro beside {BENCH}: run from a checkout "
                         "of the repository")
    for p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
        if p not in sys.path:
            sys.path.insert(0, p)
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.runtime import configure_compile_cache

    path = configure_compile_cache()
    # one cell's programs (the reference's among them) take some hundreds
    # of MB: a smaller cap evicts them and every run compiles for minutes
    cap = int(os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE") or 0)
    if 0 <= cap < CACHE_CAP:
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(CACHE_CAP)
    return path


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class WindowClosed(Exception):
    """Raised by the feed to end ``train_loop`` when the window is over."""


class TraceContext:
    """What a per-layer metric reader sees of a traced window."""

    def __init__(self, cell, events, t0, t1, steps, peaks, rank):
        from chipbench import trace

        self.events = events
        self.t0, self.t1 = t0, t1
        self.window_s = t1 - t0
        self.busy_s = trace.busy(events, t0, t1)
        self.steps = steps
        self.peaks = peaks
        self.rank = rank
        self.config = cell.config
        self.chips = cell.chips
        self.batch, self.seq_len = cell.batch, cell.seq_len
        self.tokens_per_step = cell.tokens_per_step


def load_metric(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> Dict[str, float]:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


class Window:
    """The feed of ``train_loop`` and the clock of the run.

    ``batch_at(step)`` is the one hook the loop calls before each step, so
    it takes the check's readings of the first steps, opens the window at
    step ``check_steps`` and closes it when its time (or its traced steps)
    is over.  The wrapped step functions record each step's outputs."""

    def __init__(self, cell, traffic, seconds, trace_dir, readings, half):
        self.cell, self.traffic = cell, traffic
        self.seconds = seconds
        self.trace_dir = trace_dir
        # callbacks on the state after a step: {1: fn, 2: fn} before steps
        # 1 and 2 are dispatched, "open" when the window opens
        self.readings = readings
        self.half = half
        self.outputs: List[Any] = []  # (state, metrics) of each step
        self.compiles = 0
        self.t0 = self.t1 = None
        self.check_s = 0.0
        self.window_compiles = 0
        self.steps = 0
        self._span = None

    def wrap(self, fn, kind: str):
        import jax

        def step(*args, **kwargs):
            with jax.profiler.TraceAnnotation(f"chipbench.dispatch.{kind}"):
                out = fn(*args, **kwargs)
            self.outputs.append(out)
            return out

        return step

    def _wait(self, tree) -> None:
        import jax

        with jax.profiler.TraceAnnotation("chipbench.wait"):
            jax.block_until_ready(tree)

    def batch_at(self, step: int):
        import jax

        first = self.cell.check_steps
        if step in self.readings:
            t = time.perf_counter()
            self.readings[step](self.outputs[step - 1][0])
            self.check_s += time.perf_counter() - t
        if step == first:
            self._wait(self.outputs[-1])
            t = time.perf_counter()
            self.readings["open"](self.outputs[-1][0])
            self.check_s += time.perf_counter() - t
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation("chipbench.window")
            self._span.__enter__()
            self.base_compiles = self.compiles
            self.t0 = time.perf_counter()
        elif step > first:
            done = step - first
            if self.trace_dir:
                over = done >= int(self.cell.traffic["trace_steps"])
            else:
                self._wait(self.outputs[-2][1])  # two steps in flight
                over = time.perf_counter() - self.t0 >= self.seconds
            if over:
                self._wait(self.outputs[-1])
                self.t1 = time.perf_counter()
                self._span.__exit__(None, None, None)
                if self.trace_dir:
                    jax.profiler.stop_trace()
                self.steps = done
                self.window_compiles = self.compiles - self.base_compiles
                raise WindowClosed
        with jax.profiler.TraceAnnotation("chipbench.data"):
            return self.traffic.batch_at(step, half=self.half)


class Job(NamedTuple):
    model: Any
    opt: Any
    fns: Dict[str, Any]
    tc: Any
    state: Any  # the TrainState
    traffic: Any
    key: Any
    opt_seed: int


def build_job(cell, seed: int) -> Job:
    """Set-up up to the compile: weights and optimizer state on the device
    from the seed, the optimizer, the train step and the token feed."""
    import jax

    from chipbench import cell as cell_lib
    from chipbench import traffic as traffic_lib
    from chipbench import weights as weights_lib
    from repro.configs.base import TrainConfig
    from repro.core import make_optimizer
    from repro.models import build_model
    from repro.train.state import TrainState
    from repro.train.step import make_train_step

    words = traffic_lib.seed_words(seed)
    key = weights_lib.make_key(words[:2])
    opt_seed = words[2] & 0x7FFFFFFF
    model = build_model(cell_lib.model_config(cell))
    params = weights_lib.init(key, cell.config)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if (jax.tree_util.tree_structure(want)
            != jax.tree_util.tree_structure(params)
            or [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(want)]
            != [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(params)]):
        raise SystemExit("the benchmark's weights do not have the layout "
                         "of the program's parameters")
    opt_name, opt_kw = cell_lib.optimizer_kwargs(cell, opt_seed)
    opt = make_optimizer(opt_name, params, **opt_kw)
    state = TrainState(params, opt.init(params))
    ckpt_dir = os.path.join(tempfile.gettempdir(),
                            f"chipbench-no-checkpoints-{os.getpid()}")
    tc = TrainConfig(total_steps=10 ** 9, checkpoint_every=0,
                     checkpoint_dir=ckpt_dir, async_checkpoint=False,
                     seed=opt_seed)
    fns = make_train_step(model, opt, train_cfg=tc)
    traffic = traffic_lib.Traffic(
        cell.traffic, vocab=cell.vocab, batch=cell.batch,
        seq_len=cell.seq_len, seed=seed)
    return Job(model, opt, fns, tc, state, traffic, key, opt_seed)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, fault: Optional[str] = None,
             readings: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One run; returns the result line as a dict.  ``fault`` breaks the
    timed path for the check's own tests: "unchanged" (the step returns
    its state unchanged) or "half" (the program's loss leaves half of each
    sequence out and averages over the rest).  ``readings``, where given,
    receives the program's ("program") and the reference's ("reference")
    readings and their gaps ("found")."""
    from chipbench import cell as cell_lib

    cell = cell_lib.load_cell(workload, rehearsal=rehearsal)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not rehearsal:
        if platform != "tpu":
            raise SystemExit(f"no accelerator: JAX found {platform} devices")
        if len(devices) < cell.chips:
            raise SystemExit(f"{workload} needs {cell.chips} chips, JAX "
                             f"found {len(devices)}")
    if rehearsal:  # nothing of a CPU run is worth keeping on disk
        jax.config.update("jax_enable_compilation_cache", False)
    else:  # every program of the cell, small ones too, found again
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import jax.numpy as jnp

    from chipbench import check as check_lib
    from chipbench import reference as ref_lib
    from chipbench import weights as weights_lib
    from repro.core.lowrank import LeafState, canonical_opt_state
    from repro.train.loop import train_loop

    window = None
    cache_events: Dict[str, int] = {}

    def on_compile(event, *args, **kwargs):
        if event == BACKEND_COMPILE and window is not None:
            window.compiles += 1

    def on_event(event, **kwargs):
        if event.startswith("/jax/compilation_cache/"):
            name = event.rsplit("/", 1)[-1]
            cache_events[name] = cache_events.get(name, 0) + 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_event)

    model, opt, fns, tc, state, traffic, key, opt_seed = build_job(cell, seed)
    t_built = time.perf_counter()
    layout = weights_lib.leaves(cell.config)
    stacks = [x.stack for x in layout]
    b1, b2 = opt.config.b1, opt.config.b2

    @jax.jit
    def moments(opt_state):
        canon = canonical_opt_state(opt, opt_state)
        leaf_states = jax.tree_util.tree_leaves(
            canon.leaves, is_leaf=lambda x: isinstance(x, LeafState))
        return ref_lib.moment_readings([ls.inner.m for ls in leaf_states],
                                       [ls.inner.v for ls in leaf_states],
                                       stacks, b1)

    prog: Dict[str, Any] = {}
    after: Dict[int, Any] = {}  # step: moment readings of the state after

    def at_step(done):
        def read(st):
            after[done] = moments(st.opt_state)
        return read

    def at_open(st):
        (norms, v0), (_, v1) = jax.device_get([after.pop(0), after.pop(1)])
        prog["grad_norms"] = ref_lib.named_norms(layout, norms)
        prog["second_grad_norms"] = ref_lib.named_norms(
            layout, ref_lib.second_grad_norms(v0, v1, b2))
        prog["change_norms"] = ref_lib.change_norms(key, cell.config,
                                                    st.params)
        prog["losses"] = [float(out[1]["loss"])
                          for out in window.outputs[:cell.check_steps]]

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    window = Window(cell, traffic, seconds, trace_dir,
                    {1: at_step(0), 2: at_step(1), "open": at_open},
                    half=fault == "half")
    if fault == "unchanged":
        frozen_loss = jax.jit(model.loss)

        def unchanged(st, batch, **kw):
            lval, metrics = frozen_loss(st.params, batch)
            return st, {**metrics, "loss": lval, "bad_step": jnp.zeros(())}

        fns["jit_step"] = fns["jit_refresh_step"] = unchanged
    fns["jit_step"] = window.wrap(fns["jit_step"], "hot")
    fns["jit_refresh_step"] = window.wrap(fns["jit_refresh_step"], "refresh")

    try:
        train_loop(model, opt, window, tc, fns, state=state,
                   log_every=10 ** 9, handle_signals=False)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("train_loop ended before the window closed")
    del state
    setup_s = window.t0 - T_START - window.check_s
    window_s = window.t1 - window.t0
    first = cell.check_steps
    steps = list(range(first, first + window.steps))
    refreshes = sum(1 for s in steps if s % cell.tau == 0)
    losses = [float(out[1]["loss"]) for out in window.outputs[first:]]
    failed = sum(1 for x in losses if not math.isfinite(x))
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"set-up: {t_built - T_START:.3f} s to build, "
        f"{window.t0 - t_built - window.check_s:.3f} s for the first "
        f"{cell.check_steps} steps; compile cache "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}: {cache_events}")
    log(f"{workload}: seed {seed}; {window.steps} steps in {window_s:.4f} s "
        f"({refreshes} refresh); set-up {setup_s:.3f} s, check readings "
        f"{window.check_s:.3f} s; compiles in the window "
        f"{window.window_compiles}; peak_bytes_in_use {peak}")

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    values: Dict[str, float] = {}
    breakdown = None
    if trace:
        from chipbench import trace as trace_lib

        events, spans = trace_lib.read_xplane(trace_lib.find_xplane(trace_dir))
        t0, t1 = trace_lib.window(spans, "chipbench.window")
        if not rehearsal:
            ctx = TraceContext(cell, events, t0, t1, window.steps,
                               peaks_for(devices[0].device_kind),
                               opt.config.rank)
            device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
            for m in cell.per_layer:
                val = load_metric(m["name"]).read(ctx)
                if val is not None:
                    values[m["name"]] = val
        breakdown = {
            "device_ops": [list(x) for x in trace_lib.top_ops(events, t0, t1)],
            "idle_gaps": [list(x) for x in
                          trace_lib.idle_gaps(events, spans, t0, t1)[:10]],
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    elif not rehearsal:
        for m in cell.end_to_end:
            name = m["name"]
            if name == "train_tokens_per_s":
                values[name] = (window.steps * cell.tokens_per_step
                                / window_s / cell.chips)
            elif name == "refresh_step_s":
                if refreshes != window.steps:
                    raise RuntimeError("a refresh cell ran a hot step")
                values[name] = window_s / refreshes
            elif name == "peak_hbm_gib":
                values[name] = peak / GIB
            elif name == "setup_s":
                values[name] = setup_s
            else:
                raise SystemExit(f"no reading for end-to-end metric {name!r}")

    # the check: free the program's state, then run the reference
    window.outputs.clear()
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    log(f"live arrays before the reference: {live / GIB:.3f} GiB")
    t = time.perf_counter()
    batches = [traffic.batch_at(s) for s in range(first)]
    ref = ref_lib.Reference(cell.config).run(key, opt_seed, batches, cell.tau)
    found = check_lib.gaps(prog, ref)
    if readings is not None:
        readings.update(program=prog, reference=ref, found=found)
    limits = cell.traffic["rehearsal_limits" if rehearsal else "limits"]
    correct, table = check_lib.verdict(found, limits)
    correct = correct and failed == 0
    log(f"reference {time.perf_counter() - t:.3f} s; losses program "
        f"{prog['losses']} reference {ref['losses']}")
    log("readings " + json.dumps(found))

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {
        "correct": bool(correct),
        "attempted": window.steps,
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = table
    for name, row in table.items():
        log(f"check {name} {row['value']!r} limit {row['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on the CPU: no chip, no device metric")
    args = ap.parse_args(argv)
    bootstrap()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearsal=args.rehearsal)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
