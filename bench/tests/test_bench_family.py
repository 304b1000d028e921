"""The family module a configuration names brings its weight tree, its
work counts and its plain reference: the dense family's tree is the one
the bench has always made, and a family of another shape enters the
bench from new files alone."""
import functools
import importlib.util
import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import check, runner, scopes, spec, work  # noqa: E402

DENSE = spec.family_module(BENCH.parent, "dense")


# -- a frozen copy of the bench's dense weight maker as it stood before
# the family modules took it over; the family's tree must equal it

def _frozen_shapes(model: dict) -> dict:
    d, f = model["d_model"], model["d_ff"]
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    n, vp = model["n_layers"], -(-model["vocab_size"] // 128) * 128

    def norm(prefix, lead):
        out = {f"{prefix}.g": (lead + (d,), "gain")}
        if model["norm_kind"] == "layernorm":
            out[f"{prefix}.b"] = (lead + (d,), "bias")
        return out

    leaves = {"embed.table": ((vp, d), "proj"),
              "embed.head": ((d, vp), "head")}
    for ln in ("ln1", "ln2"):
        leaves.update(norm(f"layers.{ln}", (n,)))
    leaves.update({
        "layers.attn.wq": ((n, d, h, hd), "proj"),
        "layers.attn.wk": ((n, d, kv, hd), "proj"),
        "layers.attn.wv": ((n, d, kv, hd), "proj"),
        "layers.attn.wo": ((n, h, hd, d), "proj"),
        "layers.mlp.gate": ((n, d, f), "proj"),
        "layers.mlp.up": ((n, d, f), "proj"),
        "layers.mlp.down": ((n, f, d), "proj"),
    })
    if model["qkv_bias"]:
        leaves.update({"layers.attn.bq": ((n, h, hd), "bias"),
                       "layers.attn.bk": ((n, kv, hd), "bias"),
                       "layers.attn.bv": ((n, kv, hd), "bias")})
    leaves.update(norm("final_norm", ()))
    return leaves


def _frozen_nest(flat: dict) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return tree


@functools.partial(jax.jit, static_argnames=("spec",))
def _frozen_draw(key, spec):
    flat = {}
    for i, (path, shape, kind, d) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "gain":
            v = 1.0 + 0.1 * z
        elif kind == "head":
            v = z * d ** -0.5
        else:
            v = 0.02 * z
        flat[path] = v.astype(jnp.bfloat16)
    return _frozen_nest(flat)


def _frozen_make(model: dict, seed: int) -> dict:
    spec = tuple((path, shape, kind, model["d_model"])
                 for path, (shape, kind) in _frozen_shapes(model).items())
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return _frozen_draw(key, spec)


SMALL = {"n_layers": 2, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 8, "d_ff": 48, "vocab_size": 200, "qkv_bias": True,
         "norm_kind": "layernorm"}


@pytest.mark.parametrize("model,seed", [
    (SMALL, 3),
    (SMALL, 2 ** 33 + 2 ** 31 + 5),
    (dict(SMALL, qkv_bias=False, norm_kind="rmsnorm"), 11)])
def test_dense_tree_is_the_frozen_makers_bit_for_bit(model, seed):
    got, want = DENSE.make(model, seed), _frozen_make(model, seed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert DENSE.shapes(model) == _frozen_shapes(model)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert a.dtype == b.dtype == jnp.bfloat16, path
        assert a.shape == b.shape, path
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16)), path


# -- a family of another shape, from new files only

STUB = '''"""A hybrid family for the bench's tests: four layers, attention in
two of them (one over a window of 16 keys), gated short convolutions
in the other two; its reference reads the convolution leaf."""
import jax
import jax.numpy as jnp
import numpy as np

SCOPES = ("conv", "attention", "moe")


def shapes(model):
    d, v = model["d_model"], model["vocab_size"]
    return {"embed.table": ((v, d), "proj"),
            "conv.w": ((2, 3, d), "conv"),
            "attn.wq": ((2, d, d), "proj")}


def make(model, seed):
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    flat = {}
    for i, (path, (shape, kind)) in enumerate(shapes(model).items()):
        z = jax.random.normal(jax.random.fold_in(key, i), shape)
        v = jnp.ones(shape) if kind == "conv" else 0.02 * z
        flat[path] = v.astype(jnp.bfloat16)
    tree = {}
    for path, v in flat.items():
        group, leaf = path.split(".")
        tree.setdefault(group, {})[leaf] = v
    return tree


def work(model):
    d = model["d_model"]
    return {"block_params": 7 * d * d,
            "attention": [(4, 2, 8, 0), (4, 2, 8, 16)],
            "norms": 5, "width": d,
            "head_params": d * model["vocab_size"]}


def logit_gaps(weights, model, tokens, first, picks_by_name):
    # the mean of the all-ones convolution leaf: 1, scaled to 0.001
    level = 0.001 * float(np.asarray(weights["conv"]["w"],
                                     np.float32).mean())
    n = len(tokens) - 1 - first
    return {name: np.full(n, level) for name in picks_by_name}
'''

HYB = {"n_layers": 4, "d_model": 32, "vocab_size": 64}


@pytest.fixture
def hybrid(tmp_path):
    """A copy of bench/ with a hybrid configuration, its family module,
    mix, cell and BENCHMARK.json entry added; the bytes of every file
    that was there before."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    new = {
        "configs/hyb.json": json.dumps(
            {"name": "hyb", "repo_config": "stablelm_1_6b",
             "reference": "stub_hybrid", "model": HYB,
             "engine": {"block_size": 16, "prefill_chunk": 32}}),
        "reference/stub_hybrid.py": STUB,
        "traffic/hyb_gen.json": json.dumps(
            {"loop": "closed", "prompt_tokens": {"max": 40},
             "output_tokens": {"max": 16}, "sampling": {}}),
        "cells/hyb.gen.json": json.dumps(
            {"clients": 2, "check": {"tokens": 10,
                                     "limits": {"mean_gap": 0.01}}}),
    }
    for name, text in new.items():
        (tmp_path / "bench" / name).write_text(text)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "hyb", "source": "a test",
                             "file": "bench/configs/hyb.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "hyb.gen", "config": "hyb",
                               "traffic": "hyb_gen", "chips": 1,
                               "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load(tmp_path, "hyb.gen"), before


def _records(n_prompt=5, n_served=6):
    req = SimpleNamespace(prompt=np.arange(n_prompt), greedy=True)
    return [SimpleNamespace(req=req, tokens=list(range(n_served)),
                            walls=[1.0 + i for i in range(n_served)],
                            submit=0.5)]


def test_a_family_is_added_by_new_files_only(hybrid):
    cell, before = hybrid
    # spec: the family is the module the configuration names
    assert Path(cell.family.__file__) == \
        cell.root / "bench" / "reference" / "stub_hybrid.py"
    assert cell.family is cell.family

    # the runner's weight step: the family's tree, with its own leaves
    params = runner.make_weights(cell, 2 ** 31 + 9)
    assert set(params) == {"embed", "conv", "attn"}
    assert params["conv"]["w"].shape == (2, 3, 32)

    # work: two attention layers, the windowed one reading 16 keys
    counts = cell.family.work(cell.model)
    flops, nbytes = work.attention(counts, [(99, 1)])
    assert flops == 4 * 4 * 8 * (100 + 16)
    assert nbytes == 2 * (2 * 2 * 8 * (100 + 16) + 2 * 2 * 4 * 8)
    assert work.model_flops(counts, [(99, 1)], head_rows=1) == \
        2 * 7 * 32 * 32 + flops + 2 * 32 * 64
    assert work.norms(counts, 3) == (0, 3 * 32 * 2 * (2 + 4 * 4))

    # scopes: the reduction knows the family's scope names
    op = ("%fusion.1 = f32[1] fusion()", 2e6, 5e6,
          "jit(_decode_h)/while/body/conv/mul:")
    red = scopes.reduce_events(
        [{"modules": [("jit__decode_h(1)", 0, 1e7)], "ops": [op]}], [],
        1e7, cell.family.SCOPES)
    assert red["by_scope"] == {"decode": {"conv": pytest.approx(0.003)}}

    # check: the family's reference on the family's weights
    keep: dict = {}
    correct, checks, detail = runner._check(cell, 5, _records(), (0, 10),
                                            keep)
    assert correct is True and detail["tokens"] == 6
    assert checks["mean_gap"]["value"] == pytest.approx(0.001)
    assert check.reference_model(cell)["block_size"] == 16

    # control: the control's forward gets the family's tree, and its
    # picks are judged by the family's reference
    ctl = importlib.util.spec_from_file_location("bench_control_hyb",
                                                 BENCH / "control.py")
    control = importlib.util.module_from_spec(ctl)
    ctl.loader.exec_module(control)
    seen = []

    def first_ranked(p, cfg, seqs):
        seen.append(set(p))
        return [s for _, _, s in seqs]

    control.first_ranked = first_ranked
    row = control.compare(cell, 5, keep["seqs"])
    assert seen == [{"embed", "conv", "attn"}]
    assert row["served"]["mean_gap"] == pytest.approx(0.001)
    assert row["control"]["mean_gap"] == pytest.approx(0.001)
    assert row["tokens"] == 6

    # nothing that was there before changed
    assert all(p.read_bytes() == b for p, b in before.items())
