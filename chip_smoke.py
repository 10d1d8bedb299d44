#!/usr/bin/env python3
"""Smoke test on one TPU: a ``wall_clock`` census and the explainer over its
anomalies, through ``repro.api``, in one process.

    python3 chip_smoke.py

Phases, each timed with its compile time shown apart:

1. census — ``run_census``: the repo's kernel variants (Pallas matmul tiles,
   attention blocks, SSD chunk lengths) at sizes 2048 and 4096, four
   4-matrix chains with dims in [1024, 4096], and the gram, distributive,
   solve and bilinear families at 2048. Every instance completes, the
   store merges, and every record names the device that measured it.
2. verify — for one instance of each family (one per kernel site), every
   algorithm's output on the chip against a float64 reference on the host.
3. explain — ``explain_census``: every anomaly gets a cause, costed
   against the TPU v5e roofline.

JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in the checkout's ``.jax_cache``, so a second run hits it. With
no TPU the script exits 1 before measuring anything. The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Mapping

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

from repro.launch.cli import use_compile_cache  # noqa: E402  (imports no jax)

#: Max normwise error, max|out - ref| / max|ref|, against float64. A TPU runs
#: f32 matmuls at default precision: one bf16 pass per product, ~2^-8
#: relative error each, which chains of up to three GEMMs, LU/Cholesky and
#: softmax grow to about 1e-2. A wrong tile or index is off by O(1).
TOLERANCE = 3e-2

FAMILIES: Dict[str, Dict[str, Any]] = {
    "kernel_variants": {"sites": ["matmul", "attention", "ssd"],
                        "sizes": [2048, 4096], "per_size": 1},
    "chain": {"count": 4, "n_matrices": [4], "lo": 1024, "hi": 4096},
    "gram": {"sizes": [2048], "per_size": 1},
    "distributive": {"sizes": [2048], "per_size": 1},
    "solve": {"sizes": [2048], "per_size": 1},
    "bilinear": {"sizes": [2048], "per_size": 1},
}
#: bounds each algorithm's samples so the census fits one chip call
MAX_MEASUREMENTS = 12


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and persistent
    cache hits and misses, read from ``jax.monitoring`` events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def phase(self, name: str, fn: Callable[[], Any]) -> Any:
        t0, c0 = time.perf_counter(), self.seconds
        out = fn()
        wall, comp = time.perf_counter() - t0, self.seconds - c0
        say(f"phase {name}: {wall:.1f} s wall, of which {comp:.1f} s "
            f"tracing, lowering and compiling or reading the cache "
            f"({self.hits} cache hits, {self.misses} misses so far)")
        return out


# ------------------------------------------------------- float64 references ---


def _attention(q, k, v):
    """Causal softmax attention, K/V heads shared by query-head groups."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    scores = np.where(np.tril(np.ones((s, s), bool)), scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


def _ssd(x, dt, a_log, b_mat, c_mat):
    """The Mamba-2 SSD recurrence, one token at a time:
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  y_t = h_t C_t."""
    b, s, h, p = x.shape
    rep = h // b_mat.shape[2]
    bm, cm = np.repeat(b_mat, rep, axis=2), np.repeat(c_mat, rep, axis=2)
    decay = np.exp(dt * -np.exp(a_log))
    state = np.zeros((b, h, p, bm.shape[-1]))
    y = np.empty_like(x)
    for t in range(s):
        inject = np.einsum("bhp,bhn->bhpn", x[:, t] * dt[:, t, :, None], bm[:, t])
        state = state * decay[:, t, :, None, None] + inject
        y[:, t] = np.einsum("bhpn,bhn->bhp", state, cm[:, t])
    return y


REFERENCES: Dict[str, Callable[..., np.ndarray]] = {
    "matmul": lambda a, b: a @ b,
    "attention": _attention,
    "ssd": _ssd,
    "chain": lambda *mats: np.linalg.multi_dot(mats),
    "gram": lambda a, b: (a @ a.T) @ b,
    "distributive": lambda a, b, c: (a + b) @ c,
    "solve": np.linalg.solve,
    "bilinear": lambda u, m, v: u @ m @ v,
}


def instance_inputs(inst: Any) -> List[Any]:
    """The device arrays an instance's workloads are built on, made again
    from its seed by the family's site."""
    from repro.core.family import get_family

    p = inst.params
    return list(get_family(inst.family).variant_site(p).make_inputs(int(p["seed"])))


def verify(instances: List[Any]) -> Dict[str, float]:
    """Max normwise error per family (per site for kernel variants) of every
    algorithm's chip output against the float64 host reference, for the
    first instance of each. Raises past :data:`TOLERANCE`."""
    from repro.core.sweep import instance_entry

    first: Dict[str, Any] = {}
    for inst in instances:
        key = inst.params["site"] if inst.family == "kernel_variants" else inst.family
        first.setdefault(key, inst)
    errors: Dict[str, float] = {}
    for key, inst in first.items():
        _, _, build = instance_entry(inst)
        outputs = {name: np.asarray(fn(), np.float64) for name, fn in build().items()}
        ref = REFERENCES[key](*[np.asarray(a, np.float64) for a in instance_inputs(inst)])
        scale = float(np.max(np.abs(ref)))
        worst = {name: float(np.max(np.abs(out - ref))) / scale
                 for name, out in outputs.items()}
        errors[key] = max(worst.values())
        say(f"verify {inst.uid}: {len(outputs)} algorithms, max relative "
            f"error {errors[key]:.3e} ({max(worst, key=worst.get)})")
        if errors[key] > TOLERANCE:
            raise AssertionError(
                f"{inst.uid}: outputs off the float64 reference by "
                f"{worst} > {TOLERANCE}")
    return errors


# ----------------------------------------------------------------- phases ---


def census_phase(root: str, kind: str, families: Mapping[str, Any],
                 max_measurements: int) -> List[Dict[str, Any]]:
    from repro.api import run_census
    from repro.core.sweep import merge_shards

    spec = run_census(root, backend="wall_clock", name="chip-smoke",
                      families=dict(families), n_shards=1,
                      max_measurements=max_measurements)
    records = merge_shards(spec, root)
    if len(records) != len(spec.expand()):
        raise AssertionError(f"{len(records)}/{len(spec.expand())} instances completed")
    if not os.path.exists(os.path.join(root, "merged.jsonl")):
        raise AssertionError("the complete census did not merge")
    kinds = {r.get("device_kind") for r in records}
    if kinds != {kind}:
        raise AssertionError(f"records name device kinds {kinds}, not {kind!r}")
    return records


def explain_phase(census: str, root: str, anomalies: List[str],
                  machine: str) -> List[Dict[str, Any]]:
    from repro.api import explain_census
    from repro.explain import CAUSES

    explained = explain_census(census, root)
    if [e["uid"] for e in explained] != anomalies:
        raise AssertionError("not every anomaly has exactly one explanation")
    for e in explained:
        if e["cause"] not in CAUSES or e["machine"] != machine:
            raise AssertionError(f"{e['uid']}: cause {e['cause']!r} on {e['machine']!r}")
    return explained


def run(workdir: str, kind: str, machine: str,
        families: Mapping[str, Any] = FAMILIES,
        max_measurements: int = MAX_MEASUREMENTS) -> None:
    """The three phases in ``workdir``, on the device kind JAX runs on."""
    from repro.core.family import InstanceSpec

    clock = CompileClock()
    census = os.path.join(workdir, "census")
    records = clock.phase("census", lambda: census_phase(
        census, kind, families, max_measurements))
    anomalies = [r["uid"] for r in records if r["is_anomaly"]]
    say(f"census: {len(records)} instances, {len(anomalies)} anomalies "
        f"({', '.join(anomalies)}), every record measured on {kind!r}")
    instances = [InstanceSpec.from_dict(r) for r in records]
    errors = clock.phase("verify", lambda: verify(instances))
    say("max relative error vs float64: " + ", ".join(
        f"{k} {v:.3e}" for k, v in errors.items()) + f" (tolerance {TOLERANCE})")
    explained = clock.phase("explain", lambda: explain_phase(
        census, os.path.join(workdir, "explain"), anomalies, machine))
    causes: Dict[str, int] = {}
    for e in explained:
        causes[e["cause"]] = causes.get(e["cause"], 0) + 1
    say(f"explain: {len(explained)} causes for {len(anomalies)} anomalies on "
        f"{machine}: {causes}")
    say(f"compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}: "
        f"{clock.hits} hits, {clock.misses} misses")


def main() -> int:
    use_compile_cache()
    import jax

    from repro.roofline.terms import machine_for_device

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing measured", file=sys.stderr)
        return 1
    # every program here compiles in under JAX's default one-second floor
    # for caching, so cache them all: a second run then starts warm
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = devices[0].device_kind
    machine = machine_for_device(kind).name
    say(f"device {devices[0].platform} {kind!r} x{len(devices)}, roofline {machine}")
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
        run(workdir, kind, machine)
    say(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
