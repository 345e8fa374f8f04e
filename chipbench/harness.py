"""One run of one cell: set-up, a measured window, the check of what the
window produced against the plain reference, and the metrics.

The window drives ``ServingSystem.submit`` with a ``LocalBackend`` on one
chip, as a client would.  The harness steps the coordinator's event loop
itself (``run(until=<next event>)``): every dispatch executes, and waits
for the device, inside that call, so each return is a dispatch boundary
on the host clock.  No number is taken from the coordinator's virtual
clock.

* Set-up: build the system, load every model of the workflow (the
  program's seeded loaders), then run the cell's own traffic until its
  steady shapes have run once: a segment dispatch at the full batch cap
  (``backlog``) or one whole request (``solo``).
* Window: opens at that dispatch boundary, closes at the first boundary
  at or after ``seconds``.  Requests sent in it are drained afterwards.
  Nothing may compile inside it.
* Check: the first segment dispatch of the window.  Every prompt of it is
  encoded by the reference; two of its requests, one from each half of
  the batch and drawn from the seed, are followed through the window's
  dispatches for up to ``CHAIN_STEPS`` steps (a whole 28-step scan when
  one dispatch runs it) and recomputed by the reference from the latents
  they started from; where that finished a request, its image is
  compared too.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / ".chipbench_out"
CACHE = ROOT / ".jax_cache"

# Program and kernel names as the program emits them: the jitted segment
# scan (DenoiseSegment._make_scan's ``run``), the stand-in text encoder
# (a lambda), the VAE decode, and the flash-attention Pallas kernel.
PROGRAMS = {"segment": "jit_run", "text_encoder": "jit__lambda",
            "vae": "jit_vae_decode"}
FLASH_KERNEL = r"^%mha(\.\d+)? = .*tpu_custom_call"
# steps of a request followed across the window's dispatches by the check:
# bf16 rounding is independent from step to step and averages out of a
# longer chain, while a systematic error (a lower precision) adds up
CHAIN_STEPS = 6


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip without published peaks."""


class RunFailure(RuntimeError):
    """The run cannot give a valid result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------- the cell

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """Everything ``BENCHMARK.json`` and the files it names say of one
    cell, found by name; the configuration's architecture must have its
    reference module."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (known: {sorted(cells)})")
    w = cells[workload]
    centry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / centry["file"]).read_text())
    architecture(config, root)
    bench = root / "chipbench"
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((bench / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(workload, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


def architecture(config: Dict[str, Any], root: Path = ROOT) -> Any:
    """The reference module of the configuration's ``architecture``,
    ``chipbench/reference/<architecture>.py`` under ``root``: the
    backbone's reference, counts and program fields, as the contract in
    ``chipbench/reference/__init__.py`` says."""
    name = config.get("architecture")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"architecture (got {name!r})")
    path = root / "chipbench" / "reference" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"configuration {config.get('name')!r}: architecture "
                         f"{name!r} has no reference module {path}")
    return _load_module(path)


@functools.lru_cache(maxsize=None)
def _load_module(path: Path) -> Any:
    name = f"chipbench_architecture_{path.stem}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # a dataclass looks its module up in sys.modules while it is built
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def check_device(chips: int) -> Any:
    """The first device, after refusing a run off a TPU, on too few chips
    or on a kind with no published peaks."""
    import jax

    from chipbench.peaks import UnknownDevice, peaks_for

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips, the cell needs {chips}")
    try:
        peaks_for(devs[0].device_kind)
    except UnknownDevice as e:
        raise NoChip(str(e)) from None
    return devs[0]


def use_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in the
    checkout, for every program however small."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def family_for(config: Dict[str, Any], check_published: bool = True,
               root: Path = ROOT) -> Any:
    """The program's family, served at the configuration's sizes (its
    architecture's ``program_fields`` over the published geometry).  With
    ``check_published``, every field that no key of ``reduced`` sets must
    equal the program's published geometry."""
    import jax.numpy as jnp
    from repro.diffusion.config import FAMILIES

    arch = architecture(config, root)
    fields = arch.program_fields(arch.geometry_from_config(config))
    fam = FAMILIES[config["family"]]
    pub = fam.published
    if check_published:
        skip = {arch.REDUCIBLE[k] for k in config.get("reduced", {})}
        for name, a in fields.items():
            b = getattr(pub, name)
            if name == "dtype":
                a, b = jnp.dtype(a), jnp.dtype(b)
            if name not in skip and a != b:
                raise RunFailure(f"{config['name']}: {name} {a} differs from "
                                 f"the program's published {b}")
    return dataclasses.replace(fam, dit=dataclasses.replace(pub, **fields))


# ------------------------------------------------------- compile counting

class CompileCounter:
    """Programs lowered (every jit cache miss, compiled or read from the
    persistent cache) and seconds spent lowering and compiling."""

    _EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self) -> None:
        import jax

        self.lowered = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        if event in self._EVENTS:
            self.seconds += duration


# ------------------------------------------------------- the served system

@dataclasses.dataclass
class Row:
    """One request's part of one segment dispatch."""

    rid: int
    seed: int
    prompt: str
    start: int                 # first step of the chunk
    steps: int                 # steps the chunk ran
    lat_in: Any                # latents it started from (None: the noise)
    lat_out: Any


@dataclasses.dataclass
class Tracked:
    req: Any
    t_submit: float
    t_done: Optional[float] = None
    in_window: bool = False


class Served:
    """``ServingSystem`` with a ``LocalBackend`` serving the family's
    basic workflow, driven by closed-loop clients."""

    def __init__(self, fam: Any, mix: Any, batch_cap: int, seed: int,
                 backend: Any = None) -> None:
        from repro.core import LocalBackend, ServingSystem
        from repro.diffusion import ModelSet, make_basic_workflow

        from chipbench import traffic

        self.system = ServingSystem(n_executors=1,
                                    backend=backend or LocalBackend())
        self.system.register(make_basic_workflow(fam.name, ModelSet(fam)))
        self.system.coordinator.scheduler.max_batch_cap = batch_cap
        self.workflow = f"{fam.name}:basic"
        self.statics = {"steps": mix.steps, "guidance": mix.guidance}
        self.graph = self.system.registry.instantiate(self.workflow,
                                                      **self.statics)
        self.node_of = {}
        for n in self.graph.nodes:
            for role in ("text_encoder", "segment", "vae"):
                if n.op.model_id.startswith(role + ":"):
                    self.node_of[role] = n.id
        self.gen = traffic.requests(mix, seed)
        self.live: Dict[int, Tracked] = {}
        self.finished: List[Tracked] = []
        self.rows: Dict[int, List[Row]] = {}   # dispatch index -> rows
        self.capture = False

    @property
    def coordinator(self) -> Any:
        return self.system.coordinator

    def load(self) -> None:
        """Every model of the workflow, loaded and on the device."""
        import jax

        backend = self.coordinator.backend
        comps = [backend.ensure_loaded(n.op)[0] for n in self.graph.nodes
                 if not (n.attrs.get("inline") or n.attrs.get("io_only"))]
        jax.block_until_ready([x for c in comps for x in jax.tree.leaves(c)
                               if isinstance(x, jax.Array)])

    def warm(self, role: str, n: int) -> None:
        """Run one model of the workflow on a batch of ``n`` through the
        backend, as a dispatch of ``n`` requests would."""
        import jax.numpy as jnp

        op = next(nd.op for nd in self.graph.nodes
                  if nd.id == self.node_of[role])
        cfg = op.family.dit
        if role == "text_encoder":
            kws = [{"prompt": "warm up"} for _ in range(n)]
        else:
            kws = [{"latents": jnp.zeros((1, cfg.latent_size, cfg.latent_size,
                                          cfg.latent_channels), jnp.float32)}
                   for _ in range(n)]
        self.coordinator.backend.execute_batch(op, kws)

    def submit(self, in_window: bool) -> None:
        inputs = next(self.gen)
        t = time.perf_counter()
        req = self.system.submit(self.workflow, inputs=inputs, **self.statics)
        self.live[req.rid] = Tracked(req, t, in_window=in_window)

    def step(self) -> bool:
        """Run the events of the next virtual instant; True when that ran a
        dispatch (it has finished on the device when this returns)."""
        co = self.coordinator
        if not co.events:
            raise RunFailure("the event loop drained with requests in flight")
        n = len(co.dispatch_log)
        self.system.run(until=co.events[0][0])
        if self.capture:
            for i in range(n, len(co.dispatch_log)):
                self._capture(i, co.dispatch_log[i])
        return len(co.dispatch_log) > n

    def _capture(self, index: int, batch: Any) -> None:
        # the dispatch has run; its chunk is committed at the next
        # batch_done, so seg_state still holds what it started from
        if not batch.model_id.startswith("segment:"):
            return
        rows = []
        for rn in batch.nodes:
            req = rn.request
            rows.append(Row(req.rid, req.inputs["seed"], req.inputs["prompt"],
                            rn.seg_done, batch.segment_steps, rn.seg_state,
                            rn.seg_pending["latents"]))
        self.rows[index] = rows

    def reap(self, resubmit: bool) -> None:
        """Finish the requests that are done; a client whose request is
        done sends its next one when ``resubmit``."""
        import jax

        co = self.coordinator
        if co.rejected or co.shed:
            raise RunFailure(f"{len(co.rejected)} rejected, {len(co.shed)} shed")
        for rid, tr in list(self.live.items()):
            if tr.req.status == "inflight":
                continue
            if tr.req.status != "done":
                raise RunFailure(f"request {rid} ended {tr.req.status}")
            jax.block_until_ready(self.image_of(tr.req))
            tr.t_done = time.perf_counter()
            del self.live[rid]
            self.finished.append(tr)
            if resubmit:
                self.submit(tr.in_window)

    def image_of(self, req: Any) -> Any:
        return self.coordinator.engine.value_of(
            req.ref_key(req.graph.outputs["image"]))

    def output(self, req: Any, role: str, port: str) -> Any:
        return req.output_values[f"{req.rid}:{self.node_of[role]}"][port]




# ------------------------------------------------------------- the run

@dataclasses.dataclass
class Dispatch:
    model_id: str
    batch_size: int
    steps: int


@dataclasses.dataclass
class Picked:
    """What the check compares, copied to the host before the program's
    state is freed: the prompts and embeddings of one dispatch, and the
    followed requests (with their images where they finished)."""

    prompts: List[str]
    embeds: List[np.ndarray]
    chains: List[Row]
    images: List[Optional[np.ndarray]]


@dataclasses.dataclass
class Outcome:
    setup_s: float
    window_s: float                 # open to close
    dispatches: List[Dispatch]      # open to close
    traced_s: float                 # open to the end of the drain
    traced: List[Dispatch]          # open to the end of the drain
    latencies: List[float]
    compiles_in_window: int
    memory_peak_bytes: Optional[int]
    trace: Any                      # xplane.Trace, with --trace 1
    checks: Dict[str, float]
    check_seconds: float
    attempted: int
    failed: int


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, fam: Any = None, backend: Any = None,
             control: bool = False) -> Outcome:
    """Set-up, window and check of one cell on the default device.  A
    ``backend`` that already holds the loaded models is reused; with
    ``control`` the check compares the float8 reference in the program's
    place (see :func:`compare`)."""
    import jax

    from chipbench import traffic, xplane

    counter = CompileCounter()
    mix = traffic.mix_from(cell.traffic, cell.config)
    fam = fam or family_for(cell.config)
    cap = int(cell.config["batch_cap"])
    served = Served(fam, mix, cap, seed, backend)
    served.load()
    t_loaded = time.perf_counter()
    load_compile_s = counter.seconds

    # set-up: the cell's own traffic until its steady shapes have run
    solo = mix.clients == 1
    full = min(cap, mix.clients)
    for _ in range(mix.clients):
        served.submit(in_window=False)
    while True:
        ran = served.step()
        served.reap(resubmit=not solo)
        last = served.coordinator.dispatch_log[-1] if ran else None
        if solo and served.finished:
            break
        if (not solo and last is not None
                and last.model_id.startswith("segment:")
                and last.batch_size == full):
            break
    # the encoder and decoder at the full batch: each wave of finished
    # requests decodes, and their clients' next prompts encode, together
    if not solo:
        served.warm("text_encoder", full)
        served.warm("vae", full)
    t_setup = time.perf_counter()
    split = {"init_s": t_loaded - t_process - load_compile_s,
             "compile_s": counter.seconds,
             "warmup_s": t_setup - t_loaded - (counter.seconds - load_compile_s)}
    log("setup split: " + ", ".join(f"{k} {v:.3f}" for k, v in split.items()))

    # the window
    trace_dir = OUT / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    lowered0 = counter.lowered
    co = served.coordinator
    d0 = len(co.dispatch_log)
    served.capture = True
    t_open = time.perf_counter()
    if solo:
        served.submit(in_window=True)
    while True:
        ran = served.step()
        served.reap(resubmit=True)
        if ran and time.perf_counter() - t_open >= seconds:
            break
    t_close = time.perf_counter()
    d1 = len(co.dispatch_log)
    # drain the requests the window sent
    while any(tr.in_window for tr in served.live.values()):
        served.step()
        served.reap(resubmit=False)
    t_end = time.perf_counter()
    compiles = counter.lowered - lowered0
    served.capture = False
    if trace:
        jax.profiler.stop_trace()
    as_dispatch = lambda b: Dispatch(b.model_id, b.batch_size, b.segment_steps)
    log(f"window: {t_close - t_open:.3f} s, {d1 - d0} dispatches; drained "
        f"at {t_end - t_open:.3f} s; compiles in window {compiles}")
    if compiles:
        raise RunFailure(f"{compiles} programs compiled inside the window")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    sent = [tr for tr in served.finished if tr.in_window]
    rids = {r.rid for rows in served.rows.values() for r in rows}
    window = [as_dispatch(b) for b in co.dispatch_log[d0:d1]]
    traced = [as_dispatch(b) for b in co.dispatch_log[d0:]]
    failed = len(co.rejected) + len(co.shed)

    # the check: what it compares goes to the host, the program's state is
    # freed, then the reference runs
    picked = pick(served, seed, mix.steps)
    del served, co
    gc.collect()
    t0 = time.perf_counter()
    arch = architecture(cell.config)
    checks = compare(arch, arch.geometry_from_config(cell.config), mix, picked,
                     control)
    check_s = time.perf_counter() - t0
    return Outcome(
        setup_s=t_setup - t_process,
        window_s=t_close - t_open, dispatches=window,
        traced_s=t_end - t_open, traced=traced,
        latencies=[tr.t_done - tr.t_submit for tr in sent],
        compiles_in_window=compiles, memory_peak_bytes=peak,
        trace=xplane.load(str(trace_dir)) if trace else None, checks=checks,
        check_seconds=check_s, attempted=len(sent) if solo else len(rids),
        failed=failed)


def pick(served: Served, seed: int, total_steps: int) -> Picked:
    """The first segment dispatch of the window, and two of its requests
    (one from each half of the batch, drawn from the seed) followed
    through the window for up to ``CHAIN_STEPS`` steps."""
    import jax

    if not served.rows:
        raise RunFailure("no segment dispatch in the window")
    order = sorted(served.rows)
    first = served.rows[order[0]]
    by_rid = {tr.req.rid: tr.req for tr in
              list(served.finished) + list(served.live.values())}
    host = lambda x: None if x is None else np.asarray(jax.device_get(x))
    rng = np.random.default_rng(seed)
    half = len(first) // 2
    chosen = ([first[int(rng.integers(half))],
               first[half + int(rng.integers(len(first) - half))]]
              if half else first[:1])
    chains, images = [], []
    for row in chosen:
        steps, lat_out = row.steps, row.lat_out
        for i in order[1:]:
            nxt = next((r for r in served.rows[i] if r.rid == row.rid), None)
            if (nxt is None or nxt.start != row.start + steps
                    or steps + nxt.steps > CHAIN_STEPS):
                break
            steps, lat_out = steps + nxt.steps, nxt.lat_out
        chains.append(dataclasses.replace(row, steps=steps,
                                          lat_in=host(row.lat_in),
                                          lat_out=host(lat_out)))
        req = by_rid[row.rid]
        done = row.start + steps == total_steps and req.status == "done"
        images.append(host(served.image_of(req)) if done else None)
    embeds = [host(served.output(by_rid[r.rid], "text_encoder",
                                 "prompt_embeds"))[0] for r in first]
    return Picked([r.prompt for r in first], embeds, chains, images)


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare(ref: Any, g: Any, mix: Any, picked: Picked,
            control: bool = False) -> Dict[str, float]:
    """The numbers compared with their limits, each the worst of its kind,
    against the reference module ``ref`` of the cell's architecture:

    * ``text_embed_gap``: each prompt embedding of the dispatch against the
      reference encoder's;
    * ``latent_update_gap``: the change the program made to a followed
      request's latents against the reference's change over the same
      steps, from the same latents, under the reference's own embedding;
    * ``image_gap`` (where a followed request finished): its image against
      the reference's decode of the reference's own final latents.

    With ``control``, the reference computed with float8 weights is put
    in the program's place, on the same inputs.
    """
    from chipbench.reference import standins

    emb = standins.as_numpy(standins.encode(g, picked.prompts))
    got_emb = picked.embeds
    if control:
        got_emb = list(standins.as_numpy(
            standins.encode(g, picked.prompts, fp8=True)))
    out = {"text_embed_gap": max(rel_gap(p, e)
                                 for p, e in zip(got_emb, emb))}
    ref_emb = dict(zip(picked.prompts, emb))
    ctl_emb = dict(zip(picked.prompts, got_emb))
    updates, images = [], []
    for r, image in zip(picked.chains, picked.images):
        lat_in = (standins.as_numpy(ref.initial_latents(g, r.seed))
                  if r.lat_in is None else np.asarray(r.lat_in, np.float64))
        run = lambda e, fp8: ref.sample(
            g, lat_in.astype(np.float32), e[None].astype(np.float32),
            mix.steps, mix.guidance, r.start, r.start + r.steps, fp8=fp8)
        lat_ref = run(ref_emb[r.prompt], False)
        lat_out = r.lat_out
        if control:
            lat_ctl = run(ctl_emb[r.prompt], True)
            lat_out = standins.as_numpy(lat_ctl)
            if image is not None:
                image = standins.as_numpy(standins.decode(g, lat_ctl))
        updates.append(rel_gap(lat_out - lat_in,
                               standins.as_numpy(lat_ref) - lat_in))
        if image is not None:
            images.append(rel_gap(image, standins.as_numpy(
                standins.decode(g, lat_ref))))
    out["latent_update_gap"] = max(updates)
    if images:
        out["image_gap"] = max(images)
    return out


def correct(checks: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number within its limit; a number with no limit fails."""
    return all(limits.get(k) is not None and np.isfinite(v)
               and v <= limits[k] for k, v in checks.items())


# -------------------------------------------------------- per-layer metrics

@dataclasses.dataclass
class Readings:
    """What a per-layer metric reader may read: the traced window (open to
    the end of the drain) and its dispatches, and the cell's geometry with
    the reference module of its architecture, which counts it."""

    window_s: float
    dispatches: List[Dispatch]
    trace: Any
    geometry: Any
    architecture: Any
    peaks: Dict[str, float]
    programs: Dict[str, str]
    flash_kernel: str

    def segment_dispatches(self) -> List[Dispatch]:
        return [d for d in self.dispatches if d.model_id.startswith("segment:")]

    def request_steps(self) -> int:
        return sum(d.batch_size * d.steps for d in self.segment_dispatches())

    @property
    def rows_per_step(self) -> int:
        """Backbone rows in one request-step."""
        return self.architecture.rows_per_step(self.geometry)

    @property
    def attention_calls(self) -> List[Tuple[float, float]]:
        """(FLOPs, bytes) of each ``mha`` call of one backbone row-step."""
        return self.architecture.attention_calls(self.geometry)

    def device(self) -> Any:
        return self.trace.devices[0] if self.trace is not None else None

    def flops(self) -> float:
        """Operations of every dispatch of the window, counted from
        shapes: backbone steps (every row of each request-step), prompts
        encoded and images decoded."""
        from chipbench import flops as F

        g = self.geometry
        per = {"segment": self.rows_per_step
               * self.architecture.row_step_flops(g),
               "text_encoder": F.text_encoder_flops(g),
               "vae": F.vae_decode_flops(g)}
        return sum(d.batch_size * d.steps * per.get(d.model_id.split(":")[0], 0)
                   for d in self.dispatches)


def read_metric(name: str, readings: Readings,
                root: Path = ROOT) -> Optional[float]:
    """Run ``chipbench/metrics/<name>.py``'s ``read``; None when it finds
    nothing to read."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(readings)
