"""A training configuration of a second architecture, added as new files
only, run through the harness on the CPU.

The program's ``moe`` family at ``dbrx-132b``'s smoke widths (two layers,
width 64, four experts, two to a token): its reference module is
``testdata/moe_decoder.py``, which the configuration names under
``"reference"``. The test copies the benchmark's tree, adds the
configuration, its traffic, the cell's limits and the entries of
``BENCHMARK.json``, changes no file the tree had, and runs the cell there
with ``require_chip=False``: the program's step checked against that
reference, and the same step broken underneath in each way a training
cell can break.
"""
import contextlib
import dataclasses
import json
import os
import pathlib
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import harness, spec, trainfaults, trainstep  # noqa: E402

CONFIG, TRAFFIC = "dbrx-smoke", "train-smoke"
CELL = f"{CONFIG}.{TRAFFIC}"
# Set from CPU readings at this size, the step computed in float32: sound
# steps read at most 2.4e-7, 1.0e-6 and 1.6e-6 (11 seeds); each fault
# reads 1.0e-2 (the loss altered: loss_gap) or 0.13 (the rest: grad_gap)
# or more. In bfloat16 the router's near ties flip between the program
# and the reference, and a sound step's grad_gap swings from 1.4e-3 to
# 2.4e-2 from seed to seed, a factor of four under the faults' least.
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-4}


def _config() -> dict:
    """``dbrx-132b``'s smoke widths, as the program's registry states them
    (``repro/configs/dbrx_132b.py``), with Granite's job computed in
    float32 and every leaf of two or more dimensions decayed, as the
    program decays them."""
    job = json.loads((HERE / "configs" / "granite-3-2b.json").read_text()
                     )["job"]
    job["compute_dtype"] = "float32"
    job["optimizer"]["decay"] = ["tok", "out", "wq", "wk", "wv", "wo",
                                 "router", "wi", "wu", "wd", "ln1", "ln2"]
    return {
        "name": CONFIG, "kind": "train",
        "source": "src/repro/configs/dbrx_132b.py SMOKE",
        "arch": "dbrx-132b", "reference": "testdata/moe_decoder.py",
        "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 256, "num_local_experts": 4,
        "num_experts_per_tok": 2, "tie_word_embeddings": False,
        "attention_bias": False, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "aux_loss": {"load_balance": 0.01, "router_z": 0.001},
        "reduced": [], "job": job}


def add_cell(root: pathlib.Path) -> None:
    """Add the cell to the benchmark's tree at ``root`` as new files and
    entries appended to ``BENCHMARK.json``; every file the tree had stays
    as it was."""
    bench = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    for rel, obj in [
            (f"configs/{CONFIG}.json", _config()),
            (f"traffic/{TRAFFIC}.json", {
                "why": "two rows of 64 tokens a step", "endpoint": "train",
                "seq_len": 64, "batch": 2,
                "tokens": "uniform over the vocabulary, from the seed"}),
            (f"cells/{CELL}.json", {"limits": LIMITS})]:
        assert not (bench / rel).exists()
        (bench / rel).write_text(json.dumps(obj, indent=2))
    bj = json.loads((root / "BENCHMARK.json").read_text())
    old = json.loads(json.dumps(bj))
    bj["configs"].append({
        "name": CONFIG, "source": "src/repro/configs/dbrx_132b.py",
        "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": [],
        "why": "a second architecture, added as files"})
    bj["workloads"].append({"name": CELL, "config": CONFIG,
                            "traffic": TRAFFIC, "chips": 1,
                            "why": "the moe family's train step"})
    for m in bj["end_to_end"] + bj["per_layer"]:
        if "granite-3-2b.train-4k" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bj, indent=2))
    assert all(p.read_bytes() == b for p, b in before.items())
    assert bj["configs"][:-1] == old["configs"]
    assert bj["workloads"][:-1] == old["workloads"]
    for m, o in zip(bj["end_to_end"] + bj["per_layer"],
                    old["end_to_end"] + old["per_layer"]):
        assert m == o or m == dict(o, workloads=o["workloads"] + [CELL])


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A copy of the benchmark's tree with the cell added, in which
    ``spec`` finds every part; the program's registry hands out the
    architecture's smoke-size preset, whose widths the configuration
    states."""
    root = tmp_path / "tree"
    shutil.copytree(HERE, root / "benchmarks" / "chip", ignore=shutil
                    .ignore_patterns(".cache", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    add_cell(root)
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH", root / "benchmarks" / "chip")
    monkeypatch.setattr(spec, "benchmark",
                        lambda: spec.load_json(root / "BENCHMARK.json"))
    from repro.configs import base
    get = base.get_config
    monkeypatch.setattr(base, "get_config",
                        lambda arch, smoke=False: get(arch, smoke=True))
    return root


def _run(tmp_path, seed):
    return harness.run(CELL, seed, 0.5, False, cache_dir=tmp_path / "cache",
                       require_chip=False, jax_cache=False,
                       log=lambda line: None)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_the_batch",
                                   "layer_skipped", "attention_unmasked",
                                   "loss_altered"])
def test_a_moe_training_cell_added_as_files_runs_through_the_harness(
        tmp_path, tree, fault):
    """``correct`` holds for the sound step and fails for each fault."""
    with (trainfaults.planted(fault) if fault else contextlib.nullcontext()):
        res = _run(tmp_path, 2**31 + 7)
    assert res["correct"] is (fault is None), res["checks"]
    assert set(res["metrics"]) == {"step_ms", "setup_s"}
    assert res["failed"] == 0


def test_the_moe_weights_reach_the_program_and_come_back(tree):
    """The reference's weights in the program's tree: the embedding's rows
    and the untied head's columns past the vocabulary zero, and the map
    back drops them."""
    import jax
    from repro.models import model as M
    cfg = spec.load_json(tree / "benchmarks/chip/configs" / f"{CONFIG}.json")
    ref = trainstep.reference(cfg, f"configs/{CONFIG}.json")
    dm, mc = ref.dims(cfg), ref.program_config(cfg)
    assert mc.capacity_factor * mc.num_experts_per_tok == mc.num_experts
    w = ref.init_weights(jax.random.key(3), dm)
    p = trainstep.to_program(w, M.abstract_init(mc)[0], ref)
    assert p["embed"]["out"].shape == (64, 256) == w["out"].shape
    back = trainstep.from_program(p, dm.vocab, ref)
    assert set(back) == set(w)
    for k in w:
        np.testing.assert_array_equal(back[k], w[k])
    assert ref.leaf_norms(w).shape == (len(ref.leaf_names(dm)),)


def test_moe_step_flops_against_the_cost_analysis(tree):
    """One layer without remat, full attention as the program computes it
    at this length, and a capacity factor of one: the program's expert
    slots are then the ``k`` chosen of every token, as the count has them.
    The cost analysis also counts the work the model FLOPs leave out
    (norms, softmaxes, SiLU, the dispatch's scatter-add and the weighting
    of the experts' outputs, AdamW): 4.9% here, so the count may lie up to
    8% below it and never above. Counting all four experts, not the two
    chosen, would put it above."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.launch import steps as ST
    from repro.models import model as M
    from repro.train import optimizer as OPT
    cfg = spec.load_json(tree / "benchmarks/chip/configs" / f"{CONFIG}.json")
    ref = trainstep.reference(cfg, f"configs/{CONFIG}.json")
    mc = dataclasses.replace(get_config("dbrx-132b", smoke=True),
                             num_layers=1, d_model=256, d_ff=512,
                             num_heads=4, num_kv_heads=2, head_dim=64,
                             vocab_size=1024, capacity_factor=1.0,
                             remat=False)
    dm = ref.Dims(vocab=1024, d=256, layers=1, heads=4, kv_heads=2,
                  head_dim=64, ff=512, experts=4, top_k=2, load_balance=0.0,
                  router_z=0.0, eps=1e-5, rope_theta=1e4)
    hp = ST.make_opt_hparams(mc)
    p = M.abstract_init(mc)[0]
    o = jax.eval_shape(lambda p: OPT.init_state(p, hp), p)
    b = {k: jax.ShapeDtypeStruct((2, 256), jnp.int32)
         for k in ("tokens", "labels")}
    ca = jax.jit(ST.make_train_step(mc, hp)).lower(p, o, b).compile() \
        .cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    full = ref.step_flops(dm, 2, 256, causal=False)
    assert 1.0 <= ca["flops"] / full <= 1.08
