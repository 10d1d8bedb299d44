"""One run of a benchmark cell on the device JAX holds.

A run is set-up (imports, the device, one instance of the cell's shapes
through the census path, so every program is compiled or read from the
persistent cache), then a window of rounds, then the check.

A round is one census of the cell's pool in a fresh ``ShardStore``:
``run_chunked_campaign`` with ``build_sweep_session`` and
``record_from_session``, the calls ``run_shard`` makes, with the campaign
knobs of the configuration. The benchmark draws each row's ``seed`` from the
run's ``--seed``, the round and the row's index, so the seed changes the data
and the cell file fixes the shapes. The window ends at the first round end at
or after ``--seconds``.

The check, after the window: every instance of the window has a record that
agrees with the benchmark's own FLOP tables and with its own ranks; no
measured time beats the chip's roofline floor; and for a sample of the
window's instances, drawn from the seed, the answers of the callables the
timer measured lie within the configuration's limit of the reference, which
rounds each GEMM operand as the configuration states (``operands``).
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from types import ModuleType
from typing import Any, Callable, Dict, Iterator, List, Mapping, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: per-layer metric readers: ``bench/metrics/<name>.py``, each a ``read(run)``
METRICS_PACKAGE = "bench.metrics"
FAMILIES_PACKAGE = "bench.families"


def say(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with what its names point at."""

    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int = 1
    end_to_end: List[Dict[str, Any]] = field(default_factory=list)
    per_layer: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def family(self) -> ModuleType:
        return importlib.import_module(f"{FAMILIES_PACKAGE}.{self.config['family']}")


def applies(metric: Mapping[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of {sorted(cells)}")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name,
        config=load_json(os.path.join(root, config["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json")),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)],
    )


def sweep_spec(cell: Cell):
    """The cell's census as the program states it."""
    from repro.core.sweep import SweepSpec

    c = cell.config["campaign"]
    return SweepSpec(
        name=cell.name, families={cell.family.FAMILY: cell.family.grid(cell.config, cell.traffic)},
        n_shards=1, backend="wall_clock", m_per_iteration=int(c["m_per_iteration"]),
        eps=float(c["eps"]), max_measurements=int(c["max_measurements"]),
        rt_threshold=float(c["rt_threshold"]), chunk_size=int(c["chunk_size"]),
        policy=str(c["policy"]), save_every=int(c["save_every"]),
        base_seed=int(c["base_seed"]),
    )


# ------------------------------------------------------------------ spans ---


class Spans:
    """The benchmark's own host spans, written into the profiler's trace
    when the run is traced and not at all otherwise. ``beat`` is the
    campaign's heartbeat: it fires before each session build and engine
    step (``bench.step``, closed by the next span) and before each record
    append (``bench.append``)."""

    def __init__(self, on: bool) -> None:
        self.on = on
        self._open: Any = None

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def _enter(self, name: str) -> None:
        self.close()
        if self.on:
            import jax

            self._open = jax.profiler.TraceAnnotation(name)
            self._open.__enter__()

    def beat(self, commit: bool = False) -> None:
        self._enter("bench.append" if commit else "bench.step")

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        self.close()
        if not self.on:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


# ------------------------------------------------------------------ rounds ---


@dataclass
class Observed:
    """What the window left for the check and the metrics."""

    rows: Dict[str, Dict[str, Any]] = field(default_factory=dict)    #: uid -> params
    records: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    #: uid -> its least measured time over the chip's roofline floor
    floor_ratio: Dict[str, float] = field(default_factory=dict)
    kept: Dict[str, Dict[str, Callable[[], Any]]] = field(default_factory=dict)
    timings: Dict[str, float] = field(default_factory=dict)
    rounds: int = 0


class Census:
    """Rounds of the cell's census through the program's campaign loop."""

    def __init__(self, cell: Cell, spec: Any, peaks: Any, workdir: str, spans: Spans) -> None:
        self.cell, self.spec, self.peaks = cell, spec, peaks
        self.workdir, self.spans = workdir, spans
        self.family = cell.family

    def least_seconds(self, params: Mapping[str, Any]) -> Dict[str, float]:
        return {name: sum(g.least_seconds(self.peaks.flops, self.peaks.hbm_bw)[0] for g in gs)
                for name, gs in self.family.gemms(params).items()}

    def run_round(self, round_no: int, seed: int, seen: Observed,
                  keep_index: Optional[int] = None) -> None:
        """One census of the round's pool; the row at ``keep_index`` keeps
        its measured callables alive for the check."""
        from repro.core.family import InstanceSpec
        from repro.core.sweep import (ShardStore, build_sweep_session,
                                      record_from_session, run_chunked_campaign)

        rows = self.family.rows(self.cell.config, self.cell.traffic, seed, round_no)
        insts = {uid: InstanceSpec(index=i, uid=uid, family=self.family.FAMILY, params=p)
                 for i, (uid, p) in enumerate(rows)}
        floors = {uid: self.least_seconds(p) for uid, p in rows}
        keep = None if keep_index is None else rows[keep_index][0]

        def build(uid: str):
            with self.spans.span("bench.build"):
                return build_sweep_session(self.spec, insts[uid])

        def record(session) -> Dict[str, Any]:
            with self.spans.span("bench.record"):
                rec = record_from_session(session, self.spec)
                uid = session.meta["uid"]
                seen.floor_ratio[uid] = min(
                    (float(np.min(session.store.row(name))) / floors[uid][name]
                     for name in session.store.names()), default=float("inf"))
                if uid == keep:
                    seen.kept[uid] = dict(session.timer._workloads)
                return rec

        store = ShardStore(os.path.join(self.workdir, f"round-{round_no}"), 0,
                           fsync=self.spec.fsync).open()
        timings: Dict[str, float] = {}
        with self.spans.span("bench.round"):
            run_chunked_campaign(
                store, list(insts), build, record,
                chunk_size=self.spec.chunk_size, save_every=self.spec.save_every,
                policy=self.spec.policy, heartbeat=self.spans.beat, timings=timings)
            self.spans.close()
        for rec in store.records:
            seen.records[rec["uid"]] = rec
        for uid, p in rows:
            seen.rows[uid] = p
        for k, v in timings.items():
            seen.timings[k] = seen.timings.get(k, 0.0) + v
        seen.rounds += 1


def sample_round_keeps(cell: Cell, seed: int) -> Dict[int, int]:
    """round -> index of the instance whose answers are checked: one per
    round for the first ``check.instances`` rounds, drawn from the seed."""
    rng = np.random.default_rng([int(seed) % 2**64, 7])
    pool = int(cell.traffic["pool"])
    return {r: int(rng.integers(pool)) for r in range(int(cell.config["check"]["instances"]))}


# ------------------------------------------------------------------ checks ---


def check_record(rec: Mapping[str, Any], params: Mapping[str, Any], family: ModuleType,
                 campaign: Mapping[str, Any], device_kind: str) -> List[str]:
    """What is wrong with one record, by the benchmark's own FLOP table."""
    flops = {name: sum(g.flops for g in gs) for name, gs in family.gemms(params).items()}
    least = min(flops.values())
    min_set = sorted(n for n, f in flops.items() if f == least)
    bad: List[str] = []
    if {k: float(v) for k, v in rec.get("flops", {}).items()} != flops:
        bad.append("flop table")
    if list(rec.get("min_flops_algs", [])) != min_set:
        bad.append("min-FLOPs set")
    ranks = rec.get("ranks", {})
    if (not set(ranks) <= set(flops) or not set(min_set) <= set(ranks)
            or len(ranks) != len(flops) - int(rec.get("n_dropped", -1))
            or int(rec.get("p", -1)) != len(ranks)):
        bad.append("ranks do not cover the candidate set")
    elif ranks:
        best, best_sf = min(ranks.values()), min(ranks[n] for n in min_set)
        anomaly = best_sf > best or len({ranks[n] for n in min_set}) > 1
        if bool(rec.get("is_anomaly")) != anomaly:
            bad.append("anomaly verdict disagrees with the ranks")
    n = int(rec.get("measurements_per_alg", 0))
    if not int(campaign["m_per_iteration"]) <= n <= int(campaign["max_measurements"]):
        bad.append(f"{n} measurements per algorithm")
    if rec.get("device_kind") != device_kind:
        bad.append(f"device kind {rec.get('device_kind')!r}")
    return bad


def relative_error(out: np.ndarray, ref: np.ndarray) -> float:
    """||out - ref|| / ||ref|| in the Frobenius norm; inf for a wrong shape
    or a non-finite answer."""
    out = np.asarray(out, np.float64)
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        return float("inf")
    return float(np.linalg.norm(out - ref) / np.linalg.norm(ref))


def answer_errors(family: ModuleType, params: Mapping[str, Any], operands: str,
                  answers: Mapping[str, np.ndarray]) -> Dict[str, float]:
    """Each algorithm's error against the reference at the stated operand
    precision; an algorithm with no answer reads inf."""
    ref = family.reference(params, operands)
    return {name: relative_error(answers[name], r) if name in answers else float("inf")
            for name, r in ref.items()}


@dataclass
class Verdict:
    checks: Dict[str, Dict[str, float]]
    failed: int
    problems: List[str]

    @property
    def correct(self) -> bool:
        return not self.problems


def judge(cell: Cell, seen: Observed, answers: Mapping[str, Mapping[str, np.ndarray]],
          device_kind: str) -> Verdict:
    """The window's verdict: records, the roofline floor, and the answers of
    the sampled instances."""
    family, campaign = cell.family, cell.config["campaign"]
    problems: List[str] = []
    failed = set()
    missing = [uid for uid in seen.rows if uid not in seen.records]
    bad = {}
    for uid, rec in seen.records.items():
        why = check_record(rec, seen.rows[uid], family, campaign, device_kind)
        if why:
            bad[uid] = why
    fast = [uid for uid, r in seen.floor_ratio.items() if r < 1.0]
    failed.update(missing, bad, fast)
    errors: Dict[str, float] = {}
    limit = float(cell.config["check"]["err_max"])
    for uid, out in answers.items():
        errs = answer_errors(family, seen.rows[uid], cell.config["operands"], out)
        errors[uid] = max(errs.values())
        if errors[uid] > limit:
            failed.add(uid)
            worst = max(errs, key=errs.get)
            problems.append(f"{uid}: {worst} off the reference by {errors[uid]:.3e}")
    err = max(errors.values()) if errors else float("inf")
    if not errors:
        problems.append("no answer was compared")
    if missing:
        problems.append(f"{len(missing)} instances have no record: {missing[:3]}")
    for uid, why in list(bad.items())[:3]:
        problems.append(f"{uid}: {', '.join(why)}")
    floor = min(seen.floor_ratio.values(), default=float("inf"))
    if fast:
        problems.append(f"{len(fast)} instances measured a time {floor:.3f} of "
                        "the chip's roofline floor")
    checks = {
        "err": {"value": err, "max": limit},
        "missing": {"value": len(missing), "max": 0},
        "bad_records": {"value": len(bad), "max": 0},
        "time_over_floor": {"value": floor, "min": 1.0},
    }
    return Verdict(checks=checks, failed=len(failed), problems=problems)


# ------------------------------------------------------------------ the run ---


@dataclass
class Window:
    """What the per-layer metric readers read."""

    cell: Cell
    seen: Observed
    window_s: float
    compile: Dict[str, float]
    peaks: Any
    trace: Any = None

    @property
    def instances(self) -> int:
        return len(self.seen.records)

    def kernel_roofline(self, kernel: str) -> Optional[float]:
        """Least time over device time of the kernel's trace events, in %,
        with the least time of one event from the instances' GEMM shape.
        None where the trace has no such event."""
        pattern = self.cell.family.KERNELS.get(kernel)
        if self.trace is None or pattern is None:
            return None
        count, seconds = self.trace.kernel(pattern)
        if not count or seconds <= 0:
            return None
        shapes = {g for gs in self.cell.family.gemms(next(iter(self.seen.rows.values()))).values()
                  for g in gs}
        if len(shapes) != 1:
            return None
        least, _ = shapes.pop().least_seconds(self.peaks.flops, self.peaks.hbm_bw)
        return 100.0 * count * least / seconds


def read_metrics(entries: List[Mapping[str, Any]], window: Window) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for m in entries:
        reader = importlib.import_module(f"{METRICS_PACKAGE}.{m['name']}")
        value = reader.read(window)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_info(devices: List[Any]) -> Dict[str, Any]:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             peaks: Any, workdir: Optional[str] = None) -> Dict[str, Any]:
    """Set-up, the window, the check; returns the result line's object."""
    import jax

    from bench.compile_clock import CompileClock

    devices = jax.devices()[:cell.chips]
    clock = CompileClock()
    own = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="bench-")
    spans = Spans(trace)
    census = Census(cell, sweep_spec(cell), peaks, workdir, spans)
    try:
        census.run_round(-1, seed, Observed())
        setup_compile = clock.read()
        say(f"set-up: {setup_compile['hits']} compile-cache hits, "
            f"{setup_compile['misses']} misses, {setup_compile['seconds']:.2f} s "
            "tracing, lowering and compiling")
        keeps = sample_round_keeps(cell, seed)
        seen = Observed()
        trace_dir = os.path.join(workdir, "trace")
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        with spans.span("bench.window"):
            while True:
                census.run_round(seen.rounds, seed, seen, keeps.get(seen.rounds))
                if time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        in_window = CompileClock.since(setup_compile, clock.read())
        say(f"window: {seen.rounds} rounds, {len(seen.records)} instances in "
            f"{window_s:.2f} s; {in_window['hits']} compile-cache hits, "
            f"{in_window['misses']} misses, {in_window['seconds']:.2f} s tracing, "
            "lowering and compiling")
        device = device_info(devices)
        summary = None
        if trace:
            from bench.trace import find_xplane, summarize

            summary = summarize(find_xplane(trace_dir))
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
        # the timed callables' answers, fetched before the reference runs
        answers = {uid: {name: np.asarray(fn(), np.float32) for name, fn in fns.items()}
                   for uid, fns in seen.kept.items()}
        seen.kept.clear()
        verdict = judge(cell, seen, answers, devices[0].device_kind)
        window = Window(cell, seen, window_s, in_window, peaks, summary)
        if trace:
            metrics = read_metrics(cell.per_layer, window)
        else:
            metrics = read_metrics([m for m in cell.end_to_end if m["name"] != "setup_s"],
                                   window)
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result: Dict[str, Any] = {
            "correct": verdict.correct,
            "attempted": len(seen.rows),
            "failed": verdict.failed,
            "metrics": metrics,
            "device": device,
        }
        if summary is not None:
            result["breakdown"] = {"device_ops": summary.top_ops(),
                                   "idle_gaps": summary.top_idle()}
        for problem in verdict.problems:
            say(f"not correct: {problem}")
        result["checks"] = verdict.checks
        for name, c in verdict.checks.items():
            bound = (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
            say(f"check {name}: {c['value']!r} {bound}")
        return result
    finally:
        spans.close()
        clock.close()
        if own:
            shutil.rmtree(workdir, ignore_errors=True)
