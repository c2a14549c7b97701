"""Chip benchmark of the paged serving engine: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) is a model
configuration under a traffic mix. One run:

1. refuses to start without a TPU whose ``device_kind`` is in
   ``bench/peaks.json``, or with fewer chips than the cell asks for;
2. set-up (``setup_s``): turns on JAX's persistent compilation cache, draws
   the weights on the device from the seed, builds ``ContinuousEngine``
   for the mix's longest trajectory and batch, and compiles every chunk
   and decode program the mix can reach;
3. runs the mix for its lead-in (no metric reads it), then measures for
   ``--seconds``: a backlog of requests is kept queued, engine steps are
   dispatched up to ``AHEAD_STEPS`` ahead of the host's last wait for
   their tokens (``drain()``), so that a host that stands still leaves
   the chip work to do, and each token is stamped with the host clock
   when it reaches the host. The window opens on a wait for everything
   dispatched before it; when its time is up nothing more is dispatched,
   the host waits for all that was, and the window closes on the clock
   read after that wait: all of that work, over all of that time. After
   the window the backlog runs on until enough finished requests exist
   for the check (at most ``DRAIN_S`` more);
4. reads ``memory_peak_bytes``, frees the engine, and runs the plain
   reference over a sample of the finished requests: the widest gap by
   which a served token's reference logit lies below the reference's best
   must stay within the cell's limit (``bench/cells/<cell>.json``).

With ``--trace 1`` a profiler trace of the whole window gives the
per-layer metrics; the end-to-end ones come from untraced runs. The last
line of standard output is one JSON object; the numbers compared, beside
their limits, are its last key and the last lines of standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import generator, spec  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

DRAIN_S = 60.0          # how long past the window the backlog may run on
AHEAD_STEPS = 16        # engine steps dispatched ahead of the last wait:
                        # ~4.5 s of the backlog cell's ~0.28 s steps

# ModelConfig field of each configuration-file key the program reads
CONFIG_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm", "hidden_act": "activation",
}


class Refused(Exception):
    """The run cannot be made here (no chip, unknown chip, bad cell)."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# chip and program
# ---------------------------------------------------------------------------


def require_chip(n_chips: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no device: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {dev.platform}")
    if len(devices) < n_chips:
        raise Refused(f"the cell needs {n_chips} chips, JAX found "
                      f"{len(devices)}")
    try:
        return devices, spec.peaks(dev.device_kind)
    except KeyError as e:
        raise Refused(str(e))


def import_program(root: str):
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro.serve  # noqa: F401
    except ImportError as e:
        raise Refused(f"the program under test is not in {src}: {e}")


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, with every
    key the file states applied and checked."""
    from repro.models.registry import get_config
    if conf.get("attention_bias"):
        raise Refused("the program runs no attention bias")
    over = {CONFIG_FIELDS[k]: conf[k] for k in CONFIG_FIELDS if k in conf}
    return get_config(conf["registry"]).replace(param_dtype="bfloat16",
                                                **over)


class CompileClock:
    """Counts backend compiles and persistent-cache hits as JAX reports
    them, so a run can show that nothing compiled inside its window."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self):
        return (self.compiles, self.cache_hits, self.seconds)


def configure_cache() -> str:
    import jax

    from repro.launch.compile_cache import configure_compile_cache
    where = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def warm(eng, mix: dict) -> int:
    """Compile every program the mix can reach: one chunk program per
    table cover its prompts can need, one decode program per table width
    its trajectories can need, and the copy-on-write block copy. All
    writes land in the pool's garbage block 0. Returns programs run."""
    import jax
    import jax.numpy as jnp

    from repro.serve.paged_step import table_width_bucket
    bs, C = eng.block_size, eng.prefill_chunk
    cq = C // bs
    p_min, p_max = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    covers = sorted({table_width_bucket(n, chunk_blocks=cq)
                     for n in range(1, -(-p_max // bs) + 1)})
    widths = sorted({table_width_bucket(n, nb_max=eng.nb_max)
                     for n in range(-(-p_min // bs), eng.nb_max + 1)})
    # serving passes committed pools and token vector (outputs of earlier
    # programs): warm with committed ones, or each program compiles again
    # on first use
    dev = next(iter(jax.tree_util.tree_leaves(eng.params)[0].devices()))
    eng._set_pools(tuple(jax.device_put(p, dev) for p in eng._pools()))
    eng._vec = jax.device_put(eng._vec, dev)
    z = jnp.zeros
    for w in covers:
        _, _, *pools = eng._prefill_chunk_fn(
            eng.params, z((1, C), jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray([C - 1], jnp.int32), z((1, w), jnp.int32),
            z((C,), jnp.int32), z((C,), jnp.int32), *eng._pools())
        eng._set_pools(pools)
    B = eng.max_batch
    for w in widths:
        _, _, *pools = eng._decode(
            eng.params, eng._vec, z((B, w), jnp.int32),
            z((B,), jnp.int32), *eng._pools(), tile=eng.kv_tile_blocks,
            split=eng.decode_split_k)
        eng._set_pools(pools)
    eng.pool.copy_block(0, 0)
    jax.block_until_ready(eng._pools())
    return len(covers) + len(widths) + 1


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    """One request as the client sees it (host clock, seconds)."""
    prompt: np.ndarray
    max_new: int
    first: float = 0.0
    done: float = 0.0
    tokens: List[int] = dataclasses.field(default_factory=list)
    req: object = None


class Client:
    """Drives the engine as an offline batch client does: keeps the mix's
    backlog of requests queued, steps, drains, and stamps each token as it
    reaches the host."""

    def __init__(self, eng, mix: dict, requests, clock, spans: bool):
        self.eng, self.requests, self.clock = eng, requests, clock
        self.depth = mix["backlog_depth"]
        self.recs: List[Rec] = []
        self.by_id: Dict[int, Rec] = {}
        self.t0 = self.t1 = float("inf")
        self.in_window = False
        self.tokens_window = 0
        self.ahead = 0                   # steps dispatched since the wait
        self.spans = spans

    def _span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def top_up(self) -> None:
        """Keep ``depth`` requests waiting for admission."""
        with self._span("bench.schedule"):
            waiting = len(self.eng.sched.waiting)
            while waiting < self.depth and len(self.recs) < len(
                    self.requests):
                r = self.requests[len(self.recs)]
                rec = Rec(r.prompt, r.max_new)
                with self._span("bench.submit"):
                    rec.req = self.eng.submit(r.prompt, r.max_new)
                self.recs.append(rec)
                self.by_id[rec.req.req_id] = rec
                waiting += 1

    def _receive(self, events: Dict[int, List[int]], t: float) -> None:
        for rid, toks in events.items():
            rec = self.by_id[rid]
            rec.first = rec.first or t
            rec.tokens.extend(toks)
            if self.in_window:
                self.tokens_window += len(toks)
            if len(rec.tokens) >= rec.max_new:
                rec.done = t

    def tick(self) -> None:
        """One engine step dispatched; a wait for every token dispatched
        so far once ``AHEAD_STEPS`` steps are in flight."""
        self.top_up()
        with self._span("bench.step"):
            events = self.eng.step()
        if events:
            self._receive(events, self.clock())
        self.ahead += 1
        if self.ahead >= AHEAD_STEPS:
            self.drain()

    def drain(self) -> float:
        """Waits for every step dispatched so far and receives its
        tokens; returns the host clock after the wait."""
        with self._span("bench.drain"):
            events = self.eng.drain()
        t = self.clock()
        self.ahead = 0
        if events:
            self._receive(events, t)
        return t

    def open_window(self) -> None:
        self.drain()
        self.t0 = self.clock()
        self.in_window = True

    def close_window(self) -> None:
        self.t1 = self.drain()
        self.in_window = False

    def window_recs(self) -> List[Rec]:
        """The requests the window began to answer."""
        return [r for r in self.recs if self.t0 <= r.first < self.t1]

    def checkable(self, limits: dict) -> bool:
        """Whether requests finished since the window opened are enough
        for the check's sample."""
        done = [r for r in self.recs if r.done >= self.t0]
        return (len(done) >= limits["sample_requests"]
                and sum(len(r.tokens) for r in done)
                >= limits["sample_tokens"])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    window_s: float
    setup_s: float
    recs: List[Rec]                  # requests begun in the window
    tokens_window: int
    counters: Dict[str, float]       # program counters over the window
    sizes: dict
    peaks: dict
    trace: Optional[object] = None   # trace.Reduced of the traced window
    decode_rows: List[List[int]] = dataclasses.field(default_factory=list)
    chunks: List[tuple] = dataclasses.field(default_factory=list)


def counters(eng) -> Dict[str, float]:
    return {"preemptions": eng.metrics.preemptions,
            "decode_steps": eng.metrics.decode_steps,
            "prefill_chunks": eng.metrics.prefill_chunks}


class WorkTap:
    """While tracing: records each decode step's live row lengths and each
    prefill chunk's (start, length), host-side, as the engine calls its
    programs."""

    def __init__(self, eng):
        self.eng = eng
        self.rows: List[List[int]] = []
        self.chunks: List[tuple] = []
        self._decode, self._next_chunk = eng._decode, eng.sched.next_chunk

    def __enter__(self):
        eng = self.eng

        def decode(*a, **k):
            self.rows.append([r.n_cached for r in eng._rows
                              if r is not None])
            return self._decode(*a, **k)

        def next_chunk(req, c):
            m, sl = self._next_chunk(req, c)
            self.chunks.append((m, sl))
            return m, sl

        eng._decode, eng.sched.next_chunk = decode, next_chunk
        return self

    def __exit__(self, *exc):
        self.eng._decode = self._decode
        del self.eng.sched.next_chunk       # the class's method again


def pick_sample(recs: List[Rec], seed: int, n_tokens: int,
                n_requests: int) -> List[Rec]:
    """The requests the reference checks: the longest finished one, then
    others in an order drawn from the seed, until ``n_tokens`` served
    tokens in at least ``n_requests`` requests (enough that a fault in
    part of the batch's rows is unlikely to miss them all)."""
    done = sorted((r for r in recs if r.done), key=lambda r: r.req.req_id)
    if not done:
        return []
    rng = np.random.default_rng(np.random.SeedSequence(seed % 2 ** 64))
    order = [done[i] for i in rng.permutation(len(done))]
    first = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    picked = [first]
    for r in order:
        if (len(picked) >= n_requests
                and sum(len(p.tokens) for p in picked) >= n_tokens):
            break
        if all(r is not p for p in picked):
            picked.append(r)
    return picked


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    return json.dumps(out)


@dataclasses.dataclass
class Cell:
    """Everything one cell names, found by name."""
    name: str
    root: str
    bm: dict
    wl: dict
    conf: dict
    mix: dict
    limits: dict
    ref: object
    sizes: dict


def load_cell(name: str, root: str = spec.ROOT) -> Cell:
    bm = spec.benchmark(root)
    wl = spec.workload(bm, name)
    conf = spec.config(bm, wl["config"], root)
    ref = spec.reference(conf["reference"], root)
    return Cell(name, root, bm, wl, conf, spec.mix(wl["traffic"], root),
                spec.cell(name, root)["check"], ref, ref.sizes_of(conf))


@dataclasses.dataclass
class Setup:
    dev: object
    peaks: dict
    cfg: object
    weights: object
    eng: object
    clock: CompileClock
    n_programs: int


def set_up(c: Cell, seed: int, require_tpu: bool = True,
           patch_engine=None) -> Setup:
    """Device check, compile cache, weights, engine, every program."""
    import_program(c.root)
    import jax
    if require_tpu:
        devices, peaks = require_chip(c.wl["chips"])
    else:
        devices, peaks = jax.devices(), spec.peaks("TPU v5 lite", c.root)
    dev = devices[0]
    log(f"device: {dev.device_kind}, count {len(jax.devices())}, "
        f"platform {dev.platform}")
    log(f"compile cache: {configure_cache()}")
    clock = CompileClock()
    from repro.models.registry import model_fns
    from repro.serve import ContinuousEngine
    cfg = program_config(c.conf)
    weights = c.ref.make_weights(c.sizes, seed)
    want = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)),
                                  jax.eval_shape(model_fns(cfg).init,
                                                 jax.random.PRNGKey(0)))
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), weights)
    if want != got:
        raise Refused(f"weights do not match the program's tree: "
                      f"{got} != {want}")
    jax.block_until_ready(weights)
    eng = ContinuousEngine(
        cfg, weights, max_batch=c.mix["max_batch"],
        max_len=generator.max_len(c.mix), seed=0, clock=time.perf_counter,
        **c.conf["engine"])
    if patch_engine is not None:
        patch_engine(eng)
    n = warm(eng, c.mix)
    return Setup(dev, peaks, cfg, weights, eng, clock, n)


@dataclasses.dataclass
class Drive:
    client: Client
    counters: Dict[str, float]       # over the window
    compiles_lead: tuple
    compiles_window: tuple
    tap: Optional[WorkTap] = None
    xplane: Optional[str] = None


def drive(eng, mix: dict, requests, t_lead: float, seconds: float,
          clock: CompileClock, trace_dir: Optional[str] = None,
          drain_s: float = DRAIN_S, limits: Optional[dict] = None) -> Drive:
    """Lead-in from ``t_lead``, then the window of ``seconds``, then the
    backlog runs on until requests finished since the window opened are
    enough for the check ``limits`` describe (at most ``drain_s``). With
    ``trace_dir`` a profiler trace covers the window: it starts and stops
    on a wait for every dispatched step, so that the work it records is
    the work its device time did."""
    import jax
    client = Client(eng, mix, requests, time.perf_counter,
                    trace_dir is not None)
    c0 = clock.snap()
    while time.perf_counter() < t_lead + float(mix["lead_in_s"]):
        client.tick()
    tap = win = None
    if trace_dir:
        client.drain()
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        tap = WorkTap(eng).__enter__()
        win = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        win.__enter__()
    client.open_window()
    base, c_lead = counters(eng), clock.snap()
    while time.perf_counter() < client.t0 + seconds:
        client.tick()
    client.close_window()
    if trace_dir:
        win.__exit__(None, None, None)
        tap.__exit__()
        jax.profiler.stop_trace()
    c1 = clock.snap()
    end = counters(eng)
    while (client.clock() < client.t1 + drain_s and limits is not None
           and not client.checkable(limits)):
        client.tick()
    client.drain()
    return Drive(client, {k: end[k] - base[k] for k in end},
                 tuple(a - b for a, b in zip(c_lead, c0)),
                 tuple(a - b for a, b in zip(c1, c_lead)), tap,
                 trace_mod.find_xplane(trace_dir) if trace_dir else None)


REF_BUCKET = 1024       # the reference pads each sequence to a multiple


def served_gaps(c: Cell, weights, sample: List[Rec], control: bool = False):
    """Per sampled request, the reference's gap below its best at each
    served token (and the control's, with ``control``). Sequences pad to
    a multiple of ``REF_BUCKET``, so a few reference programs serve a
    cell."""
    out = []
    for r in sample:
        n = len(r.prompt) + r.max_new - 1
        seq_len = -(-n // REF_BUCKET) * REF_BUCKET
        out.append(c.ref.served_gaps(weights, c.sizes, r.prompt,
                                     r.tokens[:r.max_new], seq_len,
                                     c.mix["output_tokens"]["max"],
                                     control=control))
    return out


def run(args, root: str = spec.ROOT, require_tpu: bool = True,
        patch_engine=None) -> str:
    """One run of one cell; returns the result line. ``require_tpu`` and
    ``patch_engine`` (called on the built engine) exist for the harness's
    own tests, which run a tiny cell on the CPU with the program broken."""
    c = load_cell(args.workload, root)
    readers = {m["name"]: (m, spec.reader(m["name"], root))
               for m in spec.metrics_for(c.bm, c.name, args.trace)}
    s = set_up(c, args.seed, require_tpu, patch_engine)
    requests = generator.generate(c.mix, args.seed, c.sizes["vocab"],
                                  c.mix["lead_in_s"] + args.seconds + DRAIN_S)
    st = s.dev.memory_stats() or {}
    log(f"memory after set-up: {st.get('bytes_in_use')} bytes in use of "
        f"{st.get('bytes_limit')}; pool {s.eng.pool.hbm_bytes} bytes")
    cs = s.clock.snap()
    t_lead = time.perf_counter()
    setup_s = t_lead - T_PROCESS
    log(f"setup: {setup_s:.3f} s; {s.n_programs} programs warmed; "
        f"{cs[0]} backend compiles ({cs[2]:.3f} s), {cs[1]} "
        f"persistent-cache hits")
    trace_dir = (os.path.join(root, "bench", ".trace", c.name)
                 if args.trace else None)
    d = drive(s.eng, c.mix, requests, t_lead, args.seconds, s.clock,
              trace_dir, limits=c.limits)
    log(f"compiles: lead-in {d.compiles_lead[0]} (cache hits "
        f"{d.compiles_lead[1]}); window {d.compiles_window[0]} (cache hits "
        f"{d.compiles_window[1]})")
    import jax
    peak = int((s.dev.memory_stats() or {}).get("peak_bytes_in_use", -1))
    cl = d.client
    wrecs = cl.window_recs()
    window_s = cl.t1 - cl.t0
    log(f"window: {window_s:.3f} s, {len(wrecs)} requests begun, "
        f"{cl.tokens_window} tokens received; counters {d.counters}; "
        f"peak_bytes_in_use {peak}")
    ctx = Context(window_s, setup_s, wrecs, cl.tokens_window,
                  d.counters, c.sizes, s.peaks)
    device = {"platform": s.dev.platform, "kind": s.dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        ctx.trace = trace_mod.reduce_file(d.xplane)
        ctx.decode_rows, ctx.chunks = d.tap.rows, d.tap.chunks
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        log(f"trace: {os.path.getsize(d.xplane)} bytes, "
            f"{len(d.tap.rows)} decode steps, {len(d.tap.chunks)} chunks")
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for name, (m, rd) in readers.items():
        v = rd.read(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": m["unit"]}

    # correctness: the plain reference over a sample of finished requests,
    # once the engine and its pool are freed
    lim = c.limits
    sample = pick_sample([r for r in cl.recs if r.done >= cl.t0], args.seed,
                         lim["sample_tokens"], lim["sample_requests"])
    sample = [dataclasses.replace(r, req=None) for r in sample]
    s.eng = d = cl = None
    gc.collect()
    t_ref = time.perf_counter()
    gaps = [g for g, _ in served_gaps(c, s.weights, sample)]
    worst = max((float(g.max()) for g in gaps), default=float("inf"))
    n_tok = sum(len(g) for g in gaps)
    short = sum(int(len(r.tokens) != r.max_new) for r in sample)
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s")
    checks = {
        "gap_max": {"value": worst, "limit": lim["gap_max"]},
        "tokens_checked": {"value": n_tok, "limit": lim["sample_tokens"]},
        "requests_checked": {"value": len(sample),
                             "limit": lim["sample_requests"]},
        "wrong_length": {"value": short, "limit": 0},
    }
    correct = (worst <= lim["gap_max"] and n_tok >= lim["sample_tokens"]
               and len(sample) >= lim["sample_requests"] and short == 0)
    for name, v in checks.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return result_line(correct, len(wrecs), short, metrics, device,
                       checks, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args)
    except Refused as e:
        log(f"refused: {e}")
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
