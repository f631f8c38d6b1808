"""The benchmark's harness: one cell, one seed, one measured window.

A cell of ``BENCHMARK.json`` names a configuration (a JSON file of sizes
with a plain reference beside it, ``<name>.reference.py``) and a traffic
mix; the mix's parameters are the data file ``workloads/<cell>.json``.
Per-layer metrics are readers in ``metrics/<metric>.py``. All three are
found by name, so a cell or a metric is added by adding files. The
configuration's published keys are what the reference and the work
counts (``flops.py``) read; its ``program`` object holds the keyword
arguments of the program's own model configuration, which the harness
builds without knowing any of them (``program_config``).

A run:

1. makes the inputs (a pool of host batches) and the weights (one jitted
   call on the device) from the seed;
2. calls the program's own entry point (``finetune_vit``, ``finetune`` or
   ``finetune_distributed``) with a generator over the pool, which
   timestamps every request for a batch. The warm-up (scoring, knapsack,
   compilation, the first steps) lasts until a whole step runs with no
   compilation, and at least through step 3; on the way the generator
   reads the program's state after steps 1 and 3 for the check. The window
   opens when it hands out the next batch and, ``seconds`` later, the
   generator stops; the window closes when the entry point returns;
3. reads the peak device memory: the runtime's peak, or the compiled
   step's own footprint (arguments, outputs and temporaries) where that is
   larger, since the runtime's count leaves out the step's temporaries;
   frees the program's state, reduces the trace (``--trace 1``) and runs
   the reference over the first three steps (``correct.py``).
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import typing
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
BIG = 10 ** 9          # steps: only the generator ends the loop
MIN_WARM_REQUESTS = 4  # steps 0-2 and a clean step 3 lie in the warm-up
TRACE_SECONDS = 3.0    # traced span at the start of a traced run's window


# ------------------------------------------------------------------ layout
@dataclass
class Layout:
    """Where the harness finds what a name in BENCHMARK.json refers to."""
    benchmark: Path = REPO / "BENCHMARK.json"
    root: Path = REPO
    workload_dirs: list = field(default_factory=lambda: [BENCH / "workloads"])
    metric_dirs: list = field(default_factory=lambda: [BENCH / "metrics"])

    def spec(self) -> dict:
        return json.loads(Path(self.benchmark).read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.benchmark}")

    def config(self, name: str) -> tuple:
        """(config dict, reference module) of configuration ``name``."""
        for c in self.spec()["configs"]:
            if c["name"] == name:
                path = Path(self.root) / c["file"]
                cfg = json.loads(path.read_text())
                check_config(cfg, path)
                ref = path.with_name(path.name[:-len(".json")]
                                     + ".reference.py")
                return cfg, _load_module(ref, f"bench_ref_{name}")
        raise KeyError(f"no configuration {name!r} in {self.benchmark}")

    def traffic(self, cell: str) -> dict:
        return json.loads(_find(self.workload_dirs, f"{cell}.json")
                          .read_text())

    def metric_reader(self, name: str):
        return _load_module(_find(self.metric_dirs, f"{name}.py"),
                            f"bench_metric_{name}").read

    def metrics_of(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec()[kind]
                if "workloads" not in m or cell in m["workloads"]]


# each size or setting of the program's configuration (``group.field`` in
# a sub-object) and the published key that states it; the two must agree.
# A configuration file adds ties of its own under ``ties``.
PUBLISHED_KEY = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "resolved_head_dim": "head_dim", "d_ff": "intermediate_size",
    "vocab_size": "vocab_size", "mlp_act": "hidden_act",
    "patch": "patch_size", "image_size": "image_size",
    "n_classes": "num_labels",
    "ssm.state_dim": "mamba_d_state", "ssm.head_dim": "mamba_d_head",
    "ssm.expand": "mamba_expand", "ssm.conv_width": "mamba_d_conv",
    "ssm.chunk": "mamba_chunk_size"}


def check_config(c: dict, path) -> None:
    """Refuse, naming the key or kind, a configuration whose ``program``
    object the program's configuration class cannot take or states a size
    other than the published key's (``PUBLISHED_KEY`` and the file's
    ``ties``), whose ``ties`` are unsound (``own_ties``), or whose
    ``layer_types`` hold a kind that ``flops.py`` cannot count."""
    from bench import flops
    try:
        built = program_config(c)
        own = own_ties(c, built)
        for attr, key in {**PUBLISHED_KEY, **own}.items():
            have, want = _attr(built, attr), published(c, key)
            # a tie of the file's own holds where either side is None too
            if (attr in own or None not in (have, want)) and have != want:
                raise ValueError(f"program {attr} is {have!r} where the "
                                 f"published {key} is {want!r}")
        flops.layer_kinds(c)
    except (ValueError, TypeError) as e:
        raise ValueError(f"{path}: {e}") from None


def own_ties(c: dict, built) -> dict:
    """The file's ``ties`` ({program attribute path: published key}); each
    has to name an attribute the program's configuration has and a key the
    file states, and none may tie an attribute of ``PUBLISHED_KEY`` to
    another key."""
    ties = c.get("ties", {})
    for attr, key in ties.items():
        if PUBLISHED_KEY.get(attr, key) != key:
            raise ValueError(f"tie {attr}: {key!r} where PUBLISHED_KEY "
                             f"ties it to {PUBLISHED_KEY[attr]!r}")
        if not _has(built, attr):
            raise ValueError(f"tie {attr}: {type(built).__name__} has no "
                             f"attribute {attr!r}")
        if key not in c:
            raise ValueError(f"tie {attr}: the file states no {key!r}")
    return ties


def published(c: dict, key: str):
    """A published key's value; a head's size defaults to the width over
    the heads, as in Hugging Face's configurations."""
    if key == "head_dim":
        return c.get("head_dim") or c["hidden_size"] \
            // c["num_attention_heads"]
    return c.get(key)


def _attr(obj, path: str):
    for name in path.split("."):
        obj = getattr(obj, name, None)
    return obj


def _has(obj, path: str) -> bool:
    for name in path.split("."):
        if not hasattr(obj, name):
            return False
        obj = getattr(obj, name)
    return True


def _find(dirs, filename: str) -> Path:
    for d in dirs:
        p = Path(d) / filename
        if p.exists():
            return p
    raise FileNotFoundError(f"{filename} in none of {[str(d) for d in dirs]}")


def _load_module(path: Path, name: str):
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ compilations
class CompileCounter:
    """Counts executables built or loaded (JAX's backend-compile event)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def enable_cache():
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache/`` at the root of the checkout (a
    fixed path: the path is part of the cache's key)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def key_of(seed: int):
    """A PRNG key from a seed of any size (more than 32 bits)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


# ---------------------------------------------------------------- the feed
def _entry_frame():
    f = sys._getframe(2)
    while f is not None and not ("opt_state" in f.f_locals
                                 and "params" in f.f_locals):
        f = f.f_back
    if f is None:
        raise RuntimeError("the entry point's frame was not found")
    return f


class Feed:
    """Generator over the pool that times the run and reads the program's
    state at steps 1 and 3 (``capture(request_index, entry_locals)``).

    With ``trace`` (an object with ``start()`` and ``stop()``), the profiler
    records the first ``TRACE_SECONDS`` of the window: from its opening to
    the first request after that span. The rest of the window, from
    ``t_resume`` once the profiler has stopped, runs untraced."""

    def __init__(self, pool, seconds, counter, capture, trace=None):
        self.pool, self.seconds = pool, seconds
        self.counter, self.capture, self.trace = counter, capture, trace
        self.requests, self.compiles = [], []
        self.i_open = self.t_open = None
        self.n_window = 0
        self.compiles_open = None
        self.t_trace_end = self.n_traced = self.t_resume = None

    def end_trace(self, now):
        if self.trace is not None and self.t_trace_end is None:
            self.trace.stop()
            self.t_trace_end, self.n_traced = now, self.n_window
            self.t_resume = time.perf_counter()

    def __iter__(self):
        i = 0
        while True:
            now = time.perf_counter()
            if self.i_open is None:
                loc = _entry_frame().f_locals
                try:
                    self.capture(i, loc)
                finally:
                    # the frame keeps this snapshot of its locals, and with
                    # it the state of this step, until it is read again
                    if isinstance(loc, dict):
                        loc.clear()
            self.requests.append(now)
            self.compiles.append(self.counter.n)
            if self.i_open is None:
                if i >= MIN_WARM_REQUESTS and \
                        self.compiles[i] == self.compiles[i - 1]:
                    self.i_open, self.t_open = i, now
                    self.compiles_open = self.counter.n
                    if self.trace is not None:
                        self.trace.start()
            else:
                if now - self.t_open >= TRACE_SECONDS:
                    self.end_trace(now)
                if now - self.t_open >= self.seconds:
                    return
            if self.i_open is not None:
                self.n_window += 1
            yield self.pool[i % len(self.pool)]
            i += 1


class Tracer:
    """The profiler over a span, marked by the window span on the host.

    Host tracing is kept to the runtime's main events and the Python
    tracer is off: with both at their defaults, a ViT step took twice as
    long traced as untraced, most of it in the host's image transfer."""

    def __init__(self, trace_dir: str):
        self.dir, self.ann = trace_dir, None

    def start(self):
        import jax
        from bench.trace import WINDOW_SPAN
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self.ann.__enter__()

    def stop(self):
        import jax
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


# ----------------------------------------------------------- entry drivers
def make_optimizer(o: dict):
    from repro.optim.optimizers import adamw, sgd
    if o["name"] == "sgd":
        return sgd(o["lr"], momentum=o["momentum"])
    return adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"])


def d2ft_config(t: dict):
    from repro.configs.base import D2FTConfig
    if not t.get("d2ft", True):
        return None
    return D2FTConfig(n_microbatches=t["n_microbatches"], n_pf=t["n_pf"],
                      n_po=t["n_po"])


# the program's model configuration class of each family
PROGRAM_CLASS = {"lm": ("repro.configs.base", "ModelConfig"),
                 "vit": ("repro.models.vit", "ViTConfig")}


def program_config(c: dict):
    """The program's model configuration, built from the configuration
    file's ``program`` object (``build_dataclass``)."""
    module, name = PROGRAM_CLASS[c["family"]]
    return build_dataclass(getattr(importlib.import_module(module), name),
                           c["program"])


def build_dataclass(cls, kw: dict):
    """``cls(**kw)``, where lists become tuples and a field whose type is a
    dataclass is built from its sub-object; a key that ``cls`` has no field
    for raises, naming the key."""
    hints = typing.get_type_hints(cls)
    unknown = sorted(set(kw) - {f.name for f in dataclasses.fields(cls)})
    if unknown:
        raise ValueError(f"{cls.__name__} has no field {unknown[0]!r} "
                         f"(program keys {unknown})")
    out = {}
    for k, v in kw.items():
        sub = _dataclass_of(hints[k])
        out[k] = build_dataclass(sub, v) if sub and isinstance(v, dict) \
            else _tuples(v)
    return cls(**out)


def _dataclass_of(tp):
    """The dataclass a field's type names (``X`` or ``Optional[X]``)."""
    for t in (tp, *typing.get_args(tp)):
        if dataclasses.is_dataclass(t):
            return t
    return None


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def drive_vit(c, t, held, feed, log):
    import jax.numpy as jnp
    from repro.core.d2ft import plan_schedule
    from repro.core.scores import compute_scores, vit_blocks
    from repro.models.vit import vit_loss
    from repro.train.loop import finetune_vit

    vcfg, d2 = program_config(c), d2ft_config(t)
    M = t["n_microbatches"]

    def loss_fn(p, mb):
        return vit_loss(p, jnp.asarray(mb[0]), jnp.asarray(mb[1]), vcfg)[0]

    def schedule_fn(i, p, images, labels):
        if i:
            return None
        mbs = list(zip(np.split(images, M), np.split(labels, M)))
        bw, fw = compute_scores(loss_fn, p, vit_blocks, mbs, vcfg.n_heads,
                                backward_metric=d2.backward_score,
                                forward_metric=d2.forward_score)
        return plan_schedule(d2, bw, fw, vcfg.n_layers, vcfg.n_heads)

    return finetune_vit(held.pop(), vcfg, make_optimizer(c["optimizer"]), feed,
                        BIG, schedule_fn=schedule_fn if d2 else None,
                        n_microbatches=M, use_kernel=True, log=log)


def drive_lm(c, t, held, feed, log):
    from repro.train.loop import finetune
    return finetune(held.pop(), program_config(c), d2ft_config(t),
                    make_optimizer(c["optimizer"]), feed, steps=BIG,
                    use_kernel=True, log=log)


def drive_lm_distributed(c, t, held, feed, log):
    from repro.launch.mesh import make_data_mesh
    from repro.launch.parallel import MeshSpec, ParallelConfig
    from repro.train.loop import finetune_distributed
    n = t["data_parallel"]
    pc = ParallelConfig(mesh=MeshSpec(data=n), sync_mode=t["sync_mode"],
                        use_kernel=True)
    return finetune_distributed(held.pop(), program_config(c),
                                d2ft_config(t),
                                make_optimizer(c["optimizer"]), feed,
                                steps=BIG, mesh=make_data_mesh(n),
                                parallel=pc, log=log)


ENTRIES = {"finetune_vit": drive_vit, "finetune": drive_lm,
           "finetune_distributed": drive_lm_distributed}
# the entry point's locals that its jitted step ``step_fn`` was last called
# with, in order
STEP_ARGS = {"finetune_vit": ("params", "opt_state", "images", "labels",
                              "gates"),
             "finetune": ("params", "opt_state", "batch", "sched_args"),
             "finetune_distributed": ("params", "opt_state", "batch",
                                      "gates")}


def abstract(x):
    """The shape of a step argument as the jitted step saw it: a host
    array and an uncommitted device array carry no sharding (so that the
    lowering matches the one the entry point compiled)."""
    import jax
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    x = np.asarray(x)
    return jax.ShapeDtypeStruct(x.shape,
                                jax.dtypes.canonicalize_dtype(x.dtype))


def compiled_bytes(step) -> int:
    """Device bytes the compiled step holds while it runs: arguments,
    outputs and temporaries, less what outputs share with arguments. The
    executable comes from the compilation cache, not from a new compile."""
    fn, args = step
    ma = fn.lower(*args).compile().memory_analysis()
    if ma is None:
        raise RuntimeError("the compiled step reports no memory analysis")
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def make_pool(c, t, seed):
    from bench import data
    d = t["data"]
    if c["family"] == "vit":
        return data.image_pool(seed, t["pool"], t["batch"], c["image_size"],
                               c["num_labels"], d["noise"], d["smooth"])
    return data.token_pool(seed, t["pool"], t["batch"], t["seq"],
                           c["vocab_size"], d["order_bias"])


# -------------------------------------------------------------------- run
@dataclass
class Run:
    """What one run measured, before metrics are read from it."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    n_chips: int
    t_start: float
    feed: Feed = None
    losses: list = None
    peak_bytes: int = None         # the larger of the two readings below
    runtime_peak_bytes: int = None # the runtime's peak_bytes_in_use
    step_bytes: int = None         # the compiled step's footprint
    step: tuple = None             # (jitted step, abstract arguments)
    t_close: float = None
    table: np.ndarray = None       # [L, G, N] schedule the run planned
    captured: dict = None
    reference: dict = None         # the reference's readings (correct.py)
    trace: dict = None
    window_compiles: int = None


def model_dims(c: dict, t: dict) -> dict:
    """The shapes ``flops.py`` counts from, all read from published
    keys."""
    from bench import flops
    kinds = flops.layer_kinds(c)
    m = {"d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
         "head_dim": published(c, "head_dim"),
         "d_ff": c["intermediate_size"], "layer_kinds": kinds}
    if flops.MAMBA in kinds:
        m.update(flops.mamba_dims(c))
    if c["family"] == "vit":
        n = (c["image_size"] // c["patch_size"]) ** 2
        return dict(m, family="vit", n_kv_heads=m["n_heads"],
                    mlp_gated=False, seq=n + 1, causal=False,
                    n_classes=c["num_labels"], n_patches=n,
                    patch_dim=c["patch_size"] ** 2 * c["num_channels"])
    return dict(m, family="lm", n_kv_heads=c["num_key_value_heads"],
                mlp_gated=True, seq=t["seq"], causal=True,
                vocab=c["vocab_size"])


def schedule_table(sched, c) -> np.ndarray:
    """[L, G, N] op table of a program schedule."""
    return np.asarray(sched.table).reshape(
        c["num_hidden_layers"], sched.n_groups, -1)


def microbatch_of(batch: int, n_mb: int) -> np.ndarray:
    """Contiguous split of the batch into micro-batches (paper §III-A)."""
    return np.repeat(np.arange(n_mb), batch // n_mb)


def drive(layout: Layout, cell_name: str, seed: int, seconds: float,
          trace_dir: str | None, t_start: float, counter: CompileCounter
          ) -> Run:
    """Steps 1 and 2 of the module docstring, and the peak memory."""
    import jax
    from bench import correct

    cell = layout.cell(cell_name)
    c, ref = layout.config(cell["config"])
    t = layout.traffic(cell_name)
    run = Run(cell, c, t, seed, cell["chips"], t_start, captured={})
    pool = make_pool(c, t, seed)
    init = jax.jit(lambda k: ref.init(c, k))
    from repro.train.loop import TrainLog
    log = TrainLog()

    def capture(i, loc):
        if i == 1:
            run.captured["grad_norms"] = correct.program_grad_norms(
                loc["opt_state"], c["optimizer"])
            run.step = (loc["step_fn"], tuple(
                jax.tree.map(abstract, loc[k]) for k in STEP_ARGS[t["entry"]]))
            if loc.get("sched") is not None:
                run.table = schedule_table(loc["sched"], c)
        elif i == 3:
            p3 = loc["params"]
            if loc.get("sync_plan") is not None and \
                    t.get("sync_mode") == "zero3":
                from repro.sharding.sync import zero_reshard
                p3 = zero_reshard(p3, loc["sync_plan"], None)
            run.captured["change_norms"] = correct.change_norms(
                p3, init(key_of(seed)))

    feed = Feed(pool, seconds, counter, capture,
                Tracer(trace_dir) if trace_dir else None)
    run.feed = feed
    # the program gets the only reference to its starting weights, so that
    # they are freed once its first step has replaced them
    held = [init(key_of(seed))]
    with jax.default_matmul_precision(c["precision"]["matmul"]):
        out = ENTRIES[t["entry"]](c, t, held, feed, log)
        jax.block_until_ready(out[0])
        run.t_close = time.perf_counter()
        feed.end_trace(run.t_close)
        run.window_compiles = counter.n - feed.compiles_open
        run.runtime_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[:run.n_chips])
        run.losses = list(log.losses)
        del out
        # lowered at the precision it was compiled at, so that the cache
        # hands back the same executable
        run.step_bytes = compiled_bytes(run.step)
    run.peak_bytes = max(run.runtime_peak_bytes, run.step_bytes)
    return run


# ----------------------------------------------------------------- metrics
def end_to_end(run: Run) -> dict:
    f = run.feed
    return {
        "step_ms": (run.t_close - f.t_open) / f.n_window * 1e3,
        "peak_hbm_gb": run.peak_bytes / 1e9,
        "setup_s": f.t_open - run.t_start,
    }


def metric_context(run: Run) -> dict:
    """What the per-layer readers read from."""
    import jax
    from bench import flops
    from bench.peaks import peaks
    f, t, c = run.feed, run.traffic, run.config
    B = t["batch"]
    M = t["n_microbatches"]
    G = c["num_attention_heads"]
    table = run.table if run.table is not None else flops.full_table(
        c["num_hidden_layers"], G, M)
    mb_of = microbatch_of(B, M)
    m = model_dims(c, t)
    attn_flops, attn_bytes = flops.required_attention(m, table, mb_of)
    ssd_flops, ssd_bytes = flops.required_ssd(m, table, mb_of)
    traced = f.n_traced is not None
    steps = f.n_traced if traced else f.n_window
    return {
        "n_chips": run.n_chips,
        "peaks": peaks(jax.devices()[0].device_kind),
        "config": c,
        "traffic": t,
        "model": m,
        # numbers from the trace cover its span at the start of the window
        "steps": steps,
        # host-clock numbers come from the untraced rest of the window: the
        # profiler slows the host work between steps
        "untraced_s": run.t_close - (f.t_resume if traced else f.t_open),
        "untraced_steps": f.n_window - (f.n_traced if traced else 0),
        "warmup_s": f.t_open - f.requests[0],
        "step_flops": flops.required_step_flops(m, table, mb_of),
        "kind_flops": flops.kind_flops(m, table, mb_of),
        "attn_flops": attn_flops,
        "attn_bytes": attn_bytes,
        "ssd_flops": ssd_flops,
        "ssd_bytes": ssd_bytes,
        "kernel_ms": kernel_ms(run.trace, steps),
        "trace": run.trace,
    }


def kernel_ms(trace: dict | None, steps: int) -> dict:
    """{kernel name: device ms per step, averaged over the chips}; empty
    without a trace."""
    if trace is None:
        return {}
    devs = trace["devices"].values()
    names = {n for d in devs for n in d["kernels"]}
    return {n: sum(d["kernels"].get(n, 0.0) for d in devs) / len(devs)
            / steps * 1e3 for n in sorted(names)}


def per_layer(layout: Layout, run: Run) -> dict:
    ctx = metric_context(run)
    out = {}
    for m in layout.metrics_of(run.cell["name"], "per_layer"):
        v = layout.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def reduce_run_trace(run: Run, trace_dir: str):
    from bench import trace
    try:
        run.trace = trace.reduce_trace(trace.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


# -------------------------------------------------------------------- main
def run_cell(layout: Layout, cell_name: str, seed: int, seconds: float,
             traced: bool, t_start: float) -> dict:
    """One run; returns the result object of the last output line. The
    trace goes to a temporary directory, removed once it is reduced."""
    import jax
    from bench import correct

    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    run = drive(layout, cell_name, seed, seconds, trace_dir, t_start,
                counter)
    f = run.feed
    print(f"window: {f.n_window} steps in {run.t_close - f.t_open:.3f} s, "
          f"compilations in the window: {run.window_compiles}",
          file=sys.stderr, flush=True)
    gaps = np.diff(f.requests[f.i_open:]) * 1e3
    slow = np.flatnonzero(gaps > 1.05 * np.median(gaps))
    print(f"steps: median {np.median(gaps):.2f} ms, max {gaps.max():.2f} ms; "
          f"over 1.05 x the median: "
          + (", ".join(f"step {i} {gaps[i]:.1f} ms" for i in slow[:10])
             or "none"), file=sys.stderr, flush=True)
    print(f"memory: runtime peak {run.runtime_peak_bytes} bytes, compiled "
          f"step {run.step_bytes} bytes", file=sys.stderr, flush=True)
    if run.window_compiles:
        raise RuntimeError(f"{run.window_compiles} compilations inside the "
                           "measured window")
    losses = run.losses[f.i_open:f.i_open + f.n_window]
    failed = int(sum(not np.isfinite(x) for x in losses))
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": run.n_chips,
              "memory_peak_bytes": int(run.peak_bytes)}
    if traced:
        t0 = time.perf_counter()
        reduce_run_trace(run, trace_dir)
        print(f"trace: {f.n_traced} steps in "
              f"{f.t_trace_end - f.t_open:.3f} s, reduced in "
              f"{time.perf_counter() - t0:.1f} s; untraced: "
              f"{f.n_window - f.n_traced} steps in "
              f"{run.t_close - f.t_resume:.3f} s", file=sys.stderr, flush=True)
        devs = run.trace["devices"]
        device["busy_s"] = float(np.mean([d["busy_s"] for d in devs.values()]))
        device["window_s"] = run.trace["window_s"]
        metrics = per_layer(layout, run)
    else:
        e2e = end_to_end(run)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in layout.metrics_of(cell_name, "end_to_end")}
    checks = correct.check(layout, run)
    ok = all(ch["value"] <= ch["limit"] for ch in checks.values())
    for name, ch in checks.items():
        print(f"check {name}: {ch['value']!r} limit {ch['limit']!r}",
              file=sys.stderr, flush=True)
    result = {"correct": bool(ok), "attempted": f.n_window, "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    return result
