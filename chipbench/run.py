"""One run of one cell:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It builds ``MeshEngine(device_store=True)`` in the cell's own
shapes, loads the table to capacity from ``--seed``, runs real windows of
the cell's traffic until ``warmup_windows`` in a row compile nothing (all of
that is set-up), measures for ``--seconds``, drains, holds the run to the
plain reference (``check.py``) and prints one JSON line, last on stdout.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` wraps a
window of at most ``TRACE_SECONDS`` in the JAX profiler and reports the
cell's per-layer metrics, each taken by its own reader under ``metrics/``.

It measures on a chip only: without a TPU (or with fewer chips than the
cell asks for, or without the native host kernel and codec) it exits
non-zero and prints no result. ``BENCH_RUN`` is not read.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up counts from here: before the imports

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import check, gen, spec, trace  # noqa: E402

TRACE_SECONDS = 4.0  # a traced window is short: traces are large
DRAIN_CYCLES = 10_000
_NULL = contextlib.nullcontext()
_clock = time.perf_counter


class NoChip(RuntimeError):
    """The machine cannot measure this cell (no TPU, too few chips, or a
    native library that did not load)."""


def _warn(msg: str) -> None:
    print(f"chipbench: warning: {msg}", file=sys.stderr, flush=True)


def engine_options(config: dict) -> dict:
    """The file's ``engine`` object, held to the keywords ``MeshEngine`` takes."""
    import inspect

    from rabia_tpu.parallel import MeshEngine

    return spec.engine_options(config, inspect.signature(MeshEngine).parameters)


def build_engine(config: dict):
    from rabia_tpu.apps.vector_kv import VectorShardedKV
    from rabia_tpu.core.errors import ValidationError
    from rabia_tpu.native import build as native_build
    from rabia_tpu.parallel import MeshEngine, make_mesh

    # without g++ the pack would silently take its numpy path
    if native_build.load_hostkernel() is None or native_build.load_codec() is None:
        raise NoChip("native hostkernel or codec failed to build or load")
    options = engine_options(config)
    print(f"chipbench: engine options {json.dumps(options)}", file=sys.stderr, flush=True)
    S = int(config["n_shards"])
    # the host replicas stay empty until the final sync, which rebuilds them
    try:
        return MeshEngine(
            lambda: VectorShardedKV(S, capacity=1 << 12),
            n_shards=S,
            n_replicas=int(config["n_replicas"]),
            mesh=make_mesh(),
            window=int(config["window"]),
            device_store=True,
            device_store_kw={
                "per_shard_capacity": int(config["per_shard_capacity"]),
                "key_lanes": int(config["key_bytes"]) // 8,
                "value_width": int(config["value_bytes"]),
            },
            **options,
        )
    except ValidationError as e:
        raise spec.SpecError(
            f"{config.get('name', '?')}: the engine refused the configuration "
            f"(engine options {json.dumps(options)}): {e}"
        ) from None


class Runner:
    """Drives one engine with one cell's traffic and keeps what the metrics
    and the check need: latencies, counts, sampled replies, compiles."""

    def __init__(self, eng, generator: gen.Generator, traffic: dict, tracing: bool) -> None:
        self.eng = eng
        self.gen = generator
        self.traffic = traffic
        self.stream: list = []  # every wave submitted, in order
        self.inflight: deque = deque()  # (stream index, future, start time)
        self.picked = generator.sampler()
        self.kept: list = []  # (stream index, future) of the sampled blocks
        self.lat: list = []
        self.late: list = []  # open loop: how late each arrival was sent
        self.measuring = False
        self.submitted = self.settled = 0  # blocks, while measuring
        self.windows = 0  # dispatching cycles since the start
        self.windows_measured = 0
        self.compiles: list = []  # (window number, new signatures, measuring?)
        self._sigs = set(eng._dev._fused_cache)
        if tracing:
            from jax.profiler import TraceAnnotation

            self._span = TraceAnnotation
        else:
            self._span = lambda name: _NULL
        # each wave of the pool with its bytes on the wire, encoded once
        self.pool = [(w, *generator.encode(w)) for w in generator.pool_waves()]
        self._next = 0
        S = generator.S
        if traffic["loop"] == "closed":
            self._target = int(traffic["in_flight_windows"]) * generator.W
            self.step = self._step_closed
        elif traffic["loop"] == "open":
            self._batch = int(traffic["batch_blocks"])
            self._interval = self._batch * S / float(traffic["rate_ops"])
            self._due = None
            self.step = self._step_open
        else:
            raise spec.SpecError(f"traffic loop {traffic['loop']!r}")

    # -- one block in, settled blocks out ------------------------------------

    def submit(self, wave, block, start=None) -> None:
        i = len(self.stream)
        self.stream.append(wave)
        t = _clock()
        with self._span("chipbench.submit"):
            fut = self.eng.submit_block(block)
        self.inflight.append((i, fut, t if start is None else start))
        self.submitted += self.measuring

    def _submit_next(self, start=None) -> None:
        wave, data, sizes = self.pool[self._next % len(self.pool)]
        self._next += 1
        with self._span("chipbench.build"):
            block = self.gen.block(data, sizes)  # arrives as a block of its own
        self.submit(wave, block, start)

    def cycle(self) -> None:
        eng = self.eng
        before = eng.cycles
        with self._span("chipbench.run_cycle"):
            eng.run_cycle()
        if eng.cycles != before:
            self.windows += eng.cycles - before
            self.windows_measured += (eng.cycles - before) * self.measuring
            if eng._dev.compiled_on_last_call:
                new = sorted(map(str, set(eng._dev._fused_cache) - self._sigs))
                self._sigs = set(eng._dev._fused_cache)
                self.compiles.append((self.windows, new, self.measuring))
        with self._span("chipbench.poll"):
            self.poll()

    def poll(self) -> None:
        inflight = self.inflight
        if not inflight or not inflight[0][1].done():
            return
        t = _clock()
        while inflight and inflight[0][1].done():
            i, fut, t0 = inflight.popleft()
            if not self.measuring:
                continue
            self.settled += 1
            self.lat.append(t - t0)
            if self.picked(i):
                self.kept.append((i, fut))  # read once the window has closed

    # -- the two loops --------------------------------------------------------

    def _step_closed(self) -> None:
        while len(self.inflight) < self._target:
            self._submit_next()
        self.cycle()

    def _step_open(self) -> None:
        now = _clock()
        if self._due is None:
            self._due = now
        while self._due <= now:
            if self.measuring:
                self.late.append(now - self._due)
            for _ in range(self._batch):
                self._submit_next(start=self._due)
            self._due += self._interval
        if self.inflight:
            self.cycle()
        else:
            time.sleep(min(max(self._due - _clock(), 0.0), 0.0005))

    # -- phases -----------------------------------------------------------------

    def load(self) -> None:
        for wave in self.gen.load_waves():
            self.submit(wave, self.gen.block(*self.gen.encode(wave)))
        self.drain()

    def warm_up(self) -> None:
        """Real windows of the cell's traffic until ``warmup_windows`` in a
        row have compiled nothing (and, in an open loop, until the backlog
        that those compiles left has drained, for 10 s at the most)."""
        need = int(self.traffic["warmup_windows"])
        deadline = _clock() + 600.0
        quiet_since = self.windows  # the load's windows are not this traffic's
        while self.windows - quiet_since < need:
            self.step()
            if self.compiles:
                quiet_since = max(quiet_since, self.compiles[-1][0])
            if _clock() > deadline:
                raise RuntimeError("warm-up: windows kept compiling for 600 s")
        if self.traffic["loop"] == "open":
            patience = _clock() + 10.0
            while len(self.inflight) > 2 * self._batch and _clock() < patience:
                self.step()

    def measure(self, seconds: float) -> float:
        self.measuring = True
        t0 = _clock()
        end = t0 + seconds
        with self._span(trace.WINDOW_SPAN):
            while _clock() < end:
                self.step()
            t1 = _clock()
        self.measuring = False
        return t1 - t0

    def drain(self) -> None:
        for _ in range(DRAIN_CYCLES):
            if not self.inflight:
                return
            self.cycle()

    def served(self) -> dict:
        """``{stream index: {shard: reply frame}}`` of the sampled blocks,
        read through the future's public ``result()``."""
        out = {}
        for i, fut in self.kept:
            try:
                res = fut.result()
                out[i] = {s: bytes(res[s][0]) for s in range(self.gen.S)}
            except Exception as e:  # a reply that cannot be read is a wrong reply
                _warn(f"block {i}: replies unreadable: {e!r}")
                out[i] = dict.fromkeys(range(self.gen.S))
        return out


def _device(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def _limit(value, limit, at_least=False) -> dict:
    ok = value >= limit if at_least else value <= limit
    return {"value": value, "limit": limit, "at_least": at_least, "ok": bool(ok)}


def _verify(run: Runner, cell: spec.Cell) -> dict:
    """The check (see ``check.py``), after the window has closed and the
    pipe has drained: each number compared, beside its limit."""
    from chipbench.reference import load_reference

    eng, generator = run.eng, run.gen
    unsettled = len(run.inflight)
    lane = check.lane_faults(eng)  # before the sync, which is a demotion
    ref = load_reference(cell.config)(generator.S, generator.n_keys, generator.VW)
    served = run.served()
    expected = check.replay(ref, run.stream, {i: range(generator.S) for i in served})
    compared, wrong, first_reply = check.reply_mismatches(served, expected)
    eng.sync_to_host()
    replica_wrong, first_replica = check.replica_mismatches(eng, ref, generator)
    for why in (first_reply, first_replica):
        if why:
            print(f"chipbench: {why}", file=sys.stderr)
    return {
        "replies_compared": _limit(compared, 1, at_least=True),
        "reply_mismatches": _limit(wrong, 0),
        "replica_mismatches": _limit(replica_wrong, 0),
        "lane_faults": _limit(lane, 0),
        "unsettled_blocks": _limit(unsettled, 0),
    }


def _warnings(run: Runner) -> None:
    for at, sigs, measured in run.compiles:
        if measured:
            _warn(f"window {at} compiled inside the measured window: {sigs}")
    if run.late:
        late_p95 = float(np.percentile(run.late, 95))
        if late_p95 > 0.25 * run._interval:
            _warn(
                f"the generator ran late: p95 {late_p95 * 1e3:.2f} ms of a "
                f"{run._interval * 1e3:.2f} ms arrival interval"
            )


def run_cell(workload: str, seed: int, seconds: float, tracing: bool, *,
             root=spec.REPO_ROOT, require_chip: bool = True, t_start=None,
             engine_hook=None) -> dict:
    """The whole run; returns the result line as a dict. ``require_chip`` and
    ``engine_hook`` exist for the tests and the control (a CPU rehearsal, a
    planted fault); the command line sets neither."""
    t_start = _clock() if t_start is None else t_start
    cell = spec.load_cell(workload, root)
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found platform {devs[0].platform!r}")
    if require_chip and len(devs) < cell.chips:
        raise NoChip(f"{workload} asks for {cell.chips} chips, found {len(devs)}")
    from rabia_tpu.core.compile_cache import place_compile_cache

    place_compile_cache()
    generator = gen.Generator(seed, cell.config, cell.traffic)
    eng = build_engine(cell.config)
    run = Runner(eng, generator, cell.traffic, tracing)
    if engine_hook is not None:
        engine_hook(eng, run)
    settle = eng.metrics.histogram("commit_stage_seconds", "", {"stage": "window_settle"})
    run.load()
    run.warm_up()

    trace_dir = None
    if tracing:
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    settle0 = (settle.sum, settle.count)
    setup_s = _clock() - t_start
    try:
        window_s = run.measure(seconds)
    finally:
        if tracing:
            jax.profiler.stop_trace()
    counters = {
        "settle_sum_s": settle.sum - settle0[0],
        "settle_count": settle.count - settle0[1],
        "window_compiles": sum(1 for c in run.compiles if c[2]),
    }
    run.drain()
    device = _device(devs)
    _warnings(run)
    checks = _verify(run, cell)
    eng.close()

    S = generator.S
    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": run.submitted * S,
        "failed": checks["unsettled_blocks"]["value"] * S
        + checks["reply_mismatches"]["value"],
        "metrics": None,
        "device": device,
    }
    if not tracing:
        values = {
            "committed_ops": run.settled * S / window_s,
            "commit_p50_ms": float(np.percentile(run.lat, 50)) * 1e3 if run.lat else None,
            "commit_p95_ms": float(np.percentile(run.lat, 95)) * 1e3 if run.lat else None,
            "setup_s": setup_s,
        }
        named = cell.end_to_end
    else:
        try:
            reduced = trace.reduce(trace.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {k: reduced[k] for k in ("device_ops", "idle_gaps")}
        ctx = {
            "trace": reduced,
            "spans": reduced["spans"],
            "counters": counters,
            "config": cell.config,
            "traffic": cell.traffic,
            "windows": run.windows_measured,
            "blocks": run.submitted,
            "device_kind": device["kind"],
        }
        values = {name: read(ctx) for name, read in cell.readers.items()}
        named = cell.per_layer
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in named
        if values.get(m["name"]) is not None
    }
    result["window"] = {
        "seconds": window_s, "blocks_settled": run.settled,
        "windows": run.windows_measured, "window_compiles": counters["window_compiles"],
    }
    result["checks"] = checks  # last in the line, and the last lines of stderr
    for name, c in checks.items():
        rel = ">=" if c["at_least"] else "<="
        print(f"check {name}: {c['value']} (limit {rel} {c['limit']})", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t_start=_T_PROCESS
        )
    except NoChip as e:
        print(f"chipbench: {e}; this benchmark measures on the chip only", file=sys.stderr)
        return 2
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
