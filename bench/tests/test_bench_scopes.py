"""Reduction by scope and by nested host span, and the readers of
``kv_write_share``, ``norm_decode_roofline`` and ``host_share``, on a
hand-built trace whose answers are known."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import device, drive, runner, scopes, spec, trace, work  # noqa: E402

MS = 1_000_000_000          # picoseconds in a millisecond
SCOPES = spec.family_module(BENCH.parent, "dense").SCOPES
PATH_STAT = 1


def _event(meta, start_ms, dur_ms):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * MS)} "
            f"duration_ps: {int(dur_ms * MS)} }}")


def _line(lid, name, events):
    return (f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0 "
            + " ".join(events) + " }")


def _plane(pid, name, lines, names, paths=None):
    """A plane whose event metadata ``i`` is named ``names[i]`` and, as
    on a TPU, carries its op's path in the metadata's stats: a string,
    or a reference to a stat metadata whose name is the string."""
    metas, stats = [], [(PATH_STAT, scopes.OP_PATH_STAT)]
    for i, n in names.items():
        path = (paths or {}).get(i)
        stat = ""
        if isinstance(path, str):
            stat = (f" stats {{ metadata_id: {PATH_STAT} "
                    f"str_value: \"{path}\" }}")
        elif path:
            ref = 100 + i
            stats.append((ref, path[0]))
            stat = f" stats {{ metadata_id: {PATH_STAT} ref_value: {ref} }}"
        metas.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                     f"name: \"{n}\"{stat} }} }}")
    stat_meta = " ".join(f"stat_metadata {{ key: {k} value {{ id: {k} "
                         f"name: \"{n}\" }} }}" for k, n in stats)
    return (f"planes {{ id: {pid} name: \"{name}\" " + " ".join(lines)
            + " " + " ".join(metas) + " " + stat_meta + " }")


# Device (ms from trace start): a decode program 20-60 whose while loop
# holds a norm 21-23, a KV write 23-27, the attention kernel 27-47, a
# copy with no path 47-50, a norm 50-51 and the sampling sort 51-55,
# then a transpose 55-60 outside any scope; a prefill program 70-76
# holding an embed 70-72 and the LM head 72-76. Each op: (metadata id,
# name, path, start, duration); the attention kernel's path is kept
# by reference.
BODY = "jit(_decode_h)/while/body/closed_call/"
DEVICE_OPS = [
    (3, "%while.58 = (s32[]) while()", "jit(_decode_h)/while", 20, 40),
    (4, "%fusion.1 = f32[1] fusion()", BODY + "norm/add:", 21, 2),
    (5, "%copy.3 = f32[1] copy()", BODY + "kv_write/scatter:", 23, 4),
    (6, "%flash_e2softmax_paged.4 = f32[1] custom-call()",
     (BODY + "paged_attention/pallas_call:",), 27, 20),
    (7, "%copy.9 = f32[1] copy()", None, 47, 3),
    (8, "%fusion.5 = f32[1] fusion()", BODY + "norm/mul:", 50, 1),
    (9, "%sort.2 = f32[1] sort()", BODY + "sample/sort:", 51, 4),
    (10, "%fusion.7 = f32[1] fusion()", "jit(_decode_h)/transpose:", 55, 5),
    (11, "%fusion.8 = f32[1] fusion()",
     "jit(_prefill)/embed/jit(_take)/gather:", 70, 2),
    (12, "%fusion.9 = f32[1] fusion()", "jit(_prefill)/lm_head/dot:", 72, 4)]
# Host: start_trace ends at 5, stop_trace starts at 95 (a 90 ms window).
# A decode step nested bench.step > loop.step > engine.step >
# engine.decode > {stage, dispatch, sync, finish}, then a prefill step,
# then bench.wait.
HOST_SPANS = [("$profiler.py:101 start_trace", 0, 5),
              ("bench.step", 8, 58), ("loop.step", 9, 56),
              ("loop.admit", 9, 1), ("engine.step", 10, 53),
              ("engine.admit", 10, 1), ("engine.decode", 11, 51),
              ("engine.stage", 11, 8), ("engine.dispatch", 19, 1),
              ("engine.sync", 20, 41), ("engine.finish", 61, 1),
              ("engine.reap", 62, 1), ("loop.drain", 63, 2),
              ("bench.step", 67, 23), ("loop.step", 67, 22),
              ("engine.step", 67.5, 20.5), ("engine.prefill", 68, 17),
              ("engine.stage", 68, 1.5), ("engine.dispatch", 69.5, 0.5),
              ("engine.sync", 76, 8), ("engine.finish", 84, 1),
              ("loop.drain", 88, 1), ("bench.wait", 90, 5),
              ("$profiler.py:213 stop_trace", 95, 5)]


def _xspace():
    from jax.profiler import ProfileData
    names = {1: "jit__decode_h(2)", 2: "jit__prefill(1)"}
    names.update((m, n) for m, n, _, _, _ in DEVICE_OPS)
    dev = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Modules", [_event(1, 20, 40), _event(2, 70, 6)]),
        _line(2, "XLA Ops", [_event(m, s, d)
                             for m, _, _, s, d in DEVICE_OPS]),
    ], names, {m: p for m, _, p, _, _ in DEVICE_OPS})
    names = {i + 1: n for i, n in enumerate(dict.fromkeys(
        n for n, _, _ in HOST_SPANS))}
    ids = {n: i for i, n in names.items()}
    host = _plane(2, "/host:CPU", [
        _line(1, "python", [_event(ids[n], s, d) for n, s, d in HOST_SPANS]),
    ], names)
    return ProfileData.text_proto_to_serialized_xspace(dev + " " + host)


@pytest.fixture
def trace_dir(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace())
    return tmp_path


@pytest.fixture
def reduced(trace_dir):
    red = trace.reduce(trace_dir, ("bench.step", "bench.wait"))
    return red, scopes.reduce(trace_dir, SCOPES)


def test_seconds_per_scope_and_step(reduced):
    red, sc = reduced
    assert sc["by_scope"]["decode"] == pytest.approx(
        {"norm": 0.003, "kv_write": 0.004, "paged_attention": 0.020,
         "unscoped": 0.008, "sample": 0.004})
    assert sc["by_scope"]["prefill"] == pytest.approx(
        {"embed": 0.002, "lm_head": 0.004})
    # the decode step's ops sum to its program's time, less the 1 ms
    # inside it when no op ran
    decode_s, _ = trace.module_time(red, "decode")
    assert sum(sc["by_scope"]["decode"].values()) == \
        pytest.approx(decode_s - 0.001)
    assert scopes.scope_time(sc, "decode", "kv_write") == \
        pytest.approx(0.004)
    assert scopes.scope_time(red, "decode", "kv_write") == 0.0


def test_gaps_take_the_innermost_span(reduced):
    red, sc = reduced
    # window gaps (ms): 71-90 mostly engine.sync (8 of 19), 0-15 mostly
    # engine.stage (8), 55-65 mostly loop.drain (2 of 10)
    assert sc["scoped_idle_gaps"] == [
        ["engine.sync", pytest.approx(0.019)],
        ["engine.stage", pytest.approx(0.015)],
        ["loop.drain", pytest.approx(0.010)]]
    assert sc["idle_by_innermost_span"]["engine.sync"] == \
        pytest.approx(0.019)
    # the bench-span labels of the existing reduction are unchanged
    assert {g[0] for g in red["idle_gaps"]} <= {"bench.step", "bench.wait"}
    # busy: the while loop 15-55 and the prefill ops 65-71
    assert red["busy_s"] == pytest.approx(0.040 + 0.006)


def test_host_time_leaves_out_the_waits(reduced):
    _, sc = reduced
    # loop.step 4-60 and 62-84 (78 ms) less engine.sync 15-56 and
    # 71-79 (49 ms)
    assert sc["host_s"] == pytest.approx(0.029)


def test_op_paths_come_from_the_event_metadata(trace_dir):
    raw = next(trace_dir.rglob("*.xplane.pb")).read_bytes()
    paths = scopes.op_paths(memoryview(raw))["/device:TPU:0"]
    assert paths["%copy.3 = f32[1] copy()"] == BODY + "kv_write/scatter:"
    assert paths["%flash_e2softmax_paged.4 = f32[1] custom-call()"] == \
        BODY + "paged_attention/pallas_call:"
    assert "%copy.9 = f32[1] copy()" not in paths
    assert scopes.op_paths(memoryview(b"")) == {}


def test_innermost_label_edge_cases():
    host = [("loop.step", 0, 10), ("engine.sync", 2, 9)]
    assert scopes.innermost_label(host, 0, 10) == "engine.sync"
    assert scopes.innermost_label(host, 9, 30) == "no span"
    assert scopes.innermost_label([], 0, 1) == "no span"
    assert scopes.scope_of("jit(f)/while/body/norm/mlp/add", SCOPES) == \
        "norm"
    assert scopes.scope_of(None, SCOPES) == scopes.UNSCOPED
    assert scopes.scope_of("jit(f)/normal/add", SCOPES) == scopes.UNSCOPED


MODEL = {"n_layers": 2, "d_model": 16, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 8, "d_ff": 32, "vocab_size": 100}
COUNTS = spec.family_module(BENCH.parent, "dense").work(MODEL)


def _ctx(red):
    # one request of a 10-token prompt whose 2nd and 3rd tokens surfaced
    # in the traced slice: two decode rows
    rec = SimpleNamespace(req=SimpleNamespace(prompt=[0] * 10),
                          walls=[1.0, 2.0, 2.5], submit=0.5)
    return SimpleNamespace(
        trace=red, records=[rec], traced=(1.5, 3.0), model=MODEL,
        work=COUNTS,
        cell=SimpleNamespace(engine={"prefill_chunk": 256}),
        peak=device.peaks("TPU v5 lite"))


def _reader(name):
    return spec.metric_reader(BENCH.parent, name)


def test_readers_on_a_reduction_with_scopes(reduced):
    red, sc = reduced
    ctx = _ctx(dict(red, **sc))
    assert _reader("kv_write_share.longgen")(ctx) == pytest.approx(10.0)
    _, nbytes = work.norms(COUNTS, 2)
    assert _reader("norm_decode_roofline.longgen")(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.003)
    assert _reader("host_share.longgen")(ctx) == pytest.approx(
        100.0 * 29 / 90)


@pytest.mark.parametrize("name", ["kv_write_share.longgen",
                                  "norm_decode_roofline.longgen",
                                  "host_share.longgen"])
def test_readers_read_nothing_without_scopes(reduced, name):
    red, _ = reduced
    assert _reader(name)(_ctx(red)) is None
    assert _reader(name)(_ctx(None)) is None


def test_readers_get_both_reductions_unchanged(trace_dir):
    """``ctx.trace`` of a traced run holds the scope reduction beside
    every key of ``trace.reduce``, each value as the two give it."""
    red = trace.reduce(trace_dir, drive.SPANS)
    sc = scopes.reduce(trace_dir, SCOPES)
    got = runner.reduce_trace(trace_dir, SCOPES)
    assert not set(red) & set(sc)
    assert set(got) == set(red) | set(sc)
    assert all(got[k] == v for k, v in red.items())
    assert all(got[k] == v for k, v in sc.items())
    ctx = _ctx(got)
    assert _reader("idle_share.longgen")(ctx) == \
        _reader("idle_share.longgen")(_ctx(red))
    assert _reader("kv_write_share.longgen")(ctx) == pytest.approx(10.0)
