"""One run of a cell: set-up, window, metrics, output check."""
from __future__ import annotations

import dataclasses
import gc
import json
import re
import shutil
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import (check, device, drive, scopes, serve, spec, trace,
                     traffic)


def log(*parts) -> None:
    print(*parts, flush=True)


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Done:
    """A request as the client saw it, held apart from the engine."""
    req: object
    due: float
    submit: float
    tokens: List[int]
    walls: List[float]
    finished: bool


def _freeze(rec: drive.Record) -> Done:
    return Done(rec.req, rec.due, rec.submit, list(rec.tokens), list(rec.walls), rec.finished)


class _Compiles:
    """Counts JAX's compile events between ``start`` and ``stop``."""

    def __init__(self):
        self.events: Dict[str, int] = {}

    def _listen(self, event, *a, **kw):
        if "compil" in event:
            self.events[event] = self.events.get(event, 0) + 1

    def start(self) -> None:
        import jax
        jax.monitoring.register_event_listener(self._listen)
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def stop(self) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._listen)
        jax.monitoring.unregister_event_duration_listener(self._listen)


class _Window:
    """Snapshots of the engine's counters at the window's open and
    close, and the traced slice when ``trace_dir`` is given: it starts
    at the first step boundary ``trace_at`` seconds after the open and
    ends at the first one ``trace_s`` seconds later, so it holds whole
    engine steps; ``span`` keeps its wall times."""

    def __init__(self, eng, trace_at, trace_s, trace_dir):
        self.eng = eng
        self.span = None
        self.open = self.close = None
        self.stats_open = self.stats_close = None
        self.trace_at, self.trace_s, self.trace_dir = (trace_at, trace_s,
                                                       trace_dir)
        self.tracing = self.traced = False

    def tick(self, now: float) -> None:
        import jax
        if self.open is not None and self.stats_open is None \
                and now >= self.open:
            self.stats_open = self.eng.stats()
        if self.close is not None and self.stats_close is None \
                and now >= self.close:
            self.stats_close = self.eng.stats()
        if self.trace_dir is None or self.open is None:
            return
        start = self.open + self.trace_at
        if not (self.tracing or self.traced) and now >= start:
            jax.profiler.start_trace(str(self.trace_dir))
            self.tracing = True
            self.span = (time.perf_counter(), None)
        elif self.tracing and now >= start + self.trace_s:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.tracing:
            # the step before this one has returned its tokens to the
            # host, so the device has finished its work
            self.span = (self.span[0], time.perf_counter())
            jax.profiler.stop_trace()
            self.tracing = False
            self.traced = True


def enable_cache() -> None:
    """JAX's persistent compilation cache, every program in it, so that
    only the first run of a cell in a checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    log("compile cache:", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def make_weights(cell: spec.Cell, seed: int):
    """The served tree for ``seed``, as the configuration's family makes
    it, on the device."""
    import jax
    return jax.block_until_ready(cell.family.make(cell.model, seed))


def reduce_trace(tdir: Path, scope_names) -> dict:
    """What a per-layer reader finds under ``ctx.trace``: ``trace.reduce``
    by the bench's spans, and beside it ``scopes.reduce`` by the
    program's spans and the family's scope names. Their keys are apart,
    so neither changes what the other's readers see."""
    return {**trace.reduce(tdir, drive.SPANS),
            **scopes.reduce(tdir, scope_names)}


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             dev: dict, t_start: float,
             after_build: Optional[Callable] = None,
             keep: Optional[dict] = None) -> dict:
    """Set up, measure and check one run; returns the result object.
    ``after_build(engine)`` runs once the engine exists (tests use it to
    break the timed path); ``keep``, if given, receives the window's
    request records and the sampled sequences of the check."""
    from repro.serve.loop import AsyncEngine
    compiles = _Compiles()
    load, mix, model = cell.load, cell.traffic, cell.model
    cfg = serve.arch_config(cell)
    backends = serve.served_backends(cfg)
    log("served backends:", json.dumps(backends))
    if dev["platform"] == "tpu" and backends["paged_attention"] != "pallas":
        raise RuntimeError(f"paged attention runs no kernel: {backends}")

    params = make_weights(cell, seed)
    log(f"weights made: {time.perf_counter() - t_start:.3f} s after start")
    eng = serve.build(cell, cfg, params)
    del params
    if after_build is not None:
        after_build(eng)
    log("warm-up seconds:", json.dumps(
        serve.warm_up(eng, mix)))
    # set-up's garbage (traced and compiled programs) is collected now,
    # not by a collector pause inside the window
    gc.collect()
    gc.freeze()
    tdir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if traced else None
    win = _Window(eng, load.get("trace_at_s", 1.0),
                  load.get("trace_s", 3.0), tdir)
    loop = AsyncEngine(eng)
    driver = drive.Driver(loop, mix["sampling"], on_tick=win.tick)
    gen = traffic.Generator(mix, seed, model["vocab_size"])

    if mix["loop"] == "open":
        prime, tail = load["prime_s"], load["tail_s"]
        reqs = gen.open_loop(load["rate_per_s"], -prime, seconds + tail)
        win.open = time.perf_counter() + prime
        win.close = win.open + seconds
        compiles.start()
        driver.open_loop(reqs, win.open, win.close,
                         deadline=win.close + load["drain_s"])
        end = win.close
    else:
        queues = gen.closed_loop(load["clients"], load["requests_per_client"],
                                 mix.get("first_wave", False))
        driver.start_clients(queues)
        win.open = time.perf_counter()
        win.close = win.open + seconds
        win.tick(win.open)
        compiles.start()
        driver.closed_loop(until=win.close)
        end = time.perf_counter()
    win.stop()
    compiles.stop()
    if win.stats_close is None:
        win.stats_close = eng.stats()
    setup_s = win.open - t_start
    records = [_freeze(r) for r in driver.records]
    log("compile events inside the window:", json.dumps(compiles.events))
    log("engine stats at close:", json.dumps(win.stats_close))
    log(f"pool: {cell.engine['num_blocks']} pages of "
        f"{cell.engine['block_size']} tokens")

    e2e, attempted, failed = _end_to_end(cell, records, win.open, win.close,
                                         end, setup_s, mix["loop"])
    mem_peak = device.memory_peak_bytes(cell.chips)
    log(f"memory peak: {mem_peak} bytes")
    dev_out = dict(dev, count=cell.chips, memory_peak_bytes=mem_peak)

    out_metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    breakdown = None
    if traced:
        red = reduce_trace(tdir, cell.family.SCOPES)
        shutil.rmtree(tdir, ignore_errors=True)
        log("device idle by host span (s):",
            json.dumps(red["idle_by_host_span"]))
        log("device seconds by step and scope:", json.dumps(red["by_scope"]))
        dev_out.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["top_ops"],
                     "idle_gaps": red["scoped_idle_gaps"]}
        # what a per-layer metric reader may read
        peak = (device.peaks(dev["kind"]) if dev["platform"] == "tpu"
                else None)
        ctx = SimpleNamespace(
            cell=cell, model=model, records=records,
            window=(win.open, win.close), end=end, traced=win.span,
            stats_open=win.stats_open, stats_close=win.stats_close,
            work=cell.family.work(model), trace=red, e2e=e2e, peak=peak)
        out_metrics = {}
        readers = spec.readers(cell)
        for m in cell.per_layer:
            v = readers[m["name"]](ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the served path before the reference runs
    del driver, loop, eng, win
    gc.unfreeze()
    gc.collect()
    correct, checks, detail = _check(cell, seed, records, (0.0, end), keep)
    log("output check:", json.dumps(detail))
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": out_metrics, "device": dev_out}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def _tail(name: str, values) -> Optional[float]:
    """``<kind>_p<q>_ms`` of values in seconds."""
    m = re.fullmatch(r"[a-z]+_p(\d+)_ms", name)
    return 1e3 * pct(values, float(m.group(1))) if values else 0.0


def _end_to_end(cell, records, t_open, t_close, end, setup_s, loop_kind):
    """The cell's end-to-end metrics by name: ``setup_s``, ``tok_s``,
    ``ttft_p<q>_ms`` and ``tpot_p<q>_ms``."""
    e2e = {"setup_s": setup_s}
    names = [m["name"] for m in cell.end_to_end]
    if loop_kind == "open":
        sample = [r for r in records if t_open <= r.due < t_close]
        failed = [r for r in sample if not r.finished]
        ttft, tpot = [], []
        for r in sample:
            if r.walls:
                ttft.append(r.walls[0] - r.due)
            else:
                ttft.append(end - r.due)
            if r.finished and len(r.walls) >= 2:
                tpot.append((r.walls[-1] - r.walls[0]) / (len(r.walls) - 1))
        lag = [r.submit - r.due for r in sample]
        gaps = [b - a for r in sample for a, b in zip(r.walls, r.walls[1:])]
        for name in names:
            if name.startswith("ttft_"):
                e2e[name] = _tail(name, ttft)
            elif name.startswith("tpot_"):
                e2e[name] = _tail(name, tpot)
        log(f"open loop: {len(sample)} requests due in the window, "
            f"{len(failed)} unfinished; generator lag p95 "
            f"{1e3 * pct(lag, 95):.3f} ms; per-gap ITL p99 "
            f"{1e3 * pct(gaps, 99) if gaps else 0:.3f} ms")
        log("ttft / tpot ms by percentile:", json.dumps(
            {q: [1e3 * pct(ttft, q), 1e3 * pct(tpot, q) if tpot else None]
             for q in (50, 80, 85, 90, 95)}))
        return e2e, len(sample), len(failed)
    n = drive.tokens_between(records, t_open, end)
    e2e["tok_s"] = n / (end - t_open)
    active = [r for r in records
              if r.submit < end and (not r.walls or r.walls[-1] > t_open)]
    log(f"closed loop: {n} tokens in {end - t_open:.3f} s from "
        f"{len(active)} requests")
    return e2e, len(active), 0


def _check(cell, seed, records, window, keep=None):
    """Output check of a sample of greedy requests against the plain
    reference, on weights made again from the seed."""
    cfg_check = cell.load["check"]
    rng = np.random.default_rng([seed, 1])
    chosen = check.sample(records, rng, cfg_check["tokens"], window)
    if not chosen:
        return False, {}, {"error": "no greedy request served tokens"}
    seqs = check.sequences(chosen)
    if keep is not None:
        keep.update(records=records, seqs=seqs)
    params = make_weights(cell, seed)
    t0 = time.perf_counter()
    g = check.gaps(cell.family, params, check.reference_model(cell), seqs,
                   {"served": [s for _, _, s in seqs]})["served"]
    vals = check.numbers(g)
    correct, checks = check.judge(vals, cfg_check["limits"])
    detail = dict(vals, requests=len(chosen), tokens=int(len(g)),
                  seconds=round(time.perf_counter() - t0, 3))
    return correct, checks, detail

