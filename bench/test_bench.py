"""Tests of the benchmark itself, on the CPU at the program's smoke size.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/test_bench.py

They drive whole runs of each traffic kind through ``run_cell`` with the
look for a chip skipped, in a checkout that holds a copy of ``bench/`` plus
new files only (smoke configurations of two architectures, traffic files and
their ``BENCHMARK.json`` entries), with the program's registry handing out
its smoke configurations.  They plant faults under the timed path and see
``correct`` come out false, and check the trace reduction and the FLOP count
against XLA's cost analysis for a described v5e.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R  # noqa: E402
from bench import tracereduce  # noqa: E402

SMOKE_MODEL = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
               "intermediate_size": 128, "vocab_size": 256, "q_lora_rank": 32,
               "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
               "v_head_dim": 16, "tie_word_embeddings": True, "rms_norm_eps": 1e-05,
               "rope_theta": 10000.0}
# the program's smoke configuration of minitron-8b: a dense decoder with
# grouped-query attention and an untied output head
GQA_MODEL = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
             "vocab_size": 256, "tie_word_embeddings": False, "rms_norm_eps": 1e-05,
             "rope_theta": 10000.0}
GQA_PROGRAM = {"arch": "minitron-8b", "set": {"n_layers": "model.num_hidden_layers"},
               "check": {"d_model": "model.hidden_size", "n_heads": "model.num_attention_heads",
                         "n_kv_heads": "model.num_key_value_heads", "head_dim": "model.head_dim",
                         "d_ff": "model.intermediate_size", "vocab": "model.vocab_size",
                         "tie_embeddings": "model.tie_word_embeddings",
                         "norm_eps": "model.rms_norm_eps", "rope_theta": "model.rope_theta",
                         "param_dtype": "precision.params",
                         "compute_dtype": "precision.compute"}}
TRAFFIC = {"iv": {"kind": "interval", "warmup_steps": 3, "ckpt_every": 4, "zipf_a": 1.3},
           "st": {"kind": "steady", "warmup_steps": 3, "ckpt_every": None, "zipf_a": 1.3},
           "rs": {"kind": "resume", "warmup_steps": 6, "resume_steps": 5, "zipf_a": 1.3}}
SEED = 2**33 + 17
# From CPU readings at the smoke size over five seeds: the program's largest
# loss_gap 4.6e-4, grad_gap 6.8e-3, change_gap 2.9e-3; the fp8 control's
# smallest grad_gap 2.7e-2 and change_gap 1.1e-2; a state left unchanged
# reads loss_gap 1.7e-2 or more.
SMOKE_LIMITS = {"loss_gap": 3e-3, "grad_gap": 1.5e-2, "change_gap": 6e-3}


def _tree_files(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts and ".out" not in p.parts}


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    """A checkout: a copy of the benchmark, plus new files only: a smoke
    configuration of each architecture, three traffic files and the cells
    of each configuration under each traffic."""
    root = tmp_path_factory.mktemp("smoke")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".out"))
    mla = json.loads((ROOT / "bench/configs/minicpm3-4b.nvmm64m.json").read_text())
    mla.update(name="smoke", model=SMOKE_MODEL, job={"batch": 2, "seq": 64},
               nvcache={"log_mib": 1}, limits=SMOKE_LIMITS)
    gqa = dict(mla, name="gqa", program=GQA_PROGRAM, reference="gqa_dense",
               model=GQA_MODEL, limits=SMOKE_LIMITS)
    for conf in (mla, gqa):
        (root / f"bench/configs/{conf['name']}.json").write_text(json.dumps(conf))
    for k, v in TRAFFIC.items():
        (root / f"bench/traffic/{k}.json").write_text(json.dumps(v))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": n, "source": "test", "reduced": [],
                         "file": f"bench/configs/{n}.json", "why": "test"}
                        for n in ("smoke", "gqa")]
    bench["workloads"] = [{"name": k, "config": "smoke", "traffic": k, "chips": 1,
                           "why": "test"} for k in TRAFFIC] + \
                         [{"name": f"gqa.{k}", "config": "gqa", "traffic": k, "chips": 1,
                           "why": "test"} for k in TRAFFIC]
    use = {"train_tokens_per_s": ["iv", "st"], "ckpt_durable_s": ["iv"],
           "resume_s": ["rs"], "ckpt_read_mib_per_s": ["rs"], "nv_recovery_s": ["rs"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            cells = use.get(m["name"], ["iv"] if m["name"] not in
                            ("step_mfu", "device_idle_share") else ["iv", "st"])
            m["workloads"] = cells + [f"gqa.{c}" for c in cells]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, bench


@pytest.fixture
def smoke(smoke_root, monkeypatch):
    """The smoke checkout, with the program's registry handing out each
    architecture's smoke configuration."""
    from repro.configs import registry
    monkeypatch.setattr(registry, "get_config", registry.get_smoke)
    return smoke_root


def _run(smoke, name, trace=0, seconds=1.5, seed=SEED):
    root, bench = smoke
    return R.run_cell(name, seed, seconds, trace, bench=bench, root=root,
                      require_chip=False, out_dir=root / "out")


@pytest.mark.parametrize("cell", ["st", "gqa.st", "gqa.iv"])
def test_new_cell_is_files_only(smoke, cell):
    """A cell of either architecture runs from new files alone: the copy of
    the benchmark's files is unchanged, and so is the benchmark itself."""
    root, _ = smoke
    before = _tree_files(ROOT / "bench")
    res = _run(smoke, cell)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) >= {"train_tokens_per_s", "setup_s"}
    assert _tree_files(ROOT / "bench") == before
    copy = _tree_files(root / "bench")
    assert {k: v for k, v in copy.items() if k in before} == before


def test_interval_closes_on_whole_intervals(smoke, monkeypatch):
    seen = {}
    orig = R.Run.end_to_end

    def spy(run):
        seen["run"] = run
        return orig(run)
    monkeypatch.setattr(R.Run, "end_to_end", spy)
    res = _run(smoke, "iv")
    run = seen["run"]
    assert res["correct"], res["checks"]
    last = run.histories[0][-1]["step"]
    assert (last + 1) % TRAFFIC["iv"]["ckpt_every"] == 0
    assert run.t_close == run.commits[-1]            # closes at a manifest commit
    assert run.t_close - run.t_open >= 1.5
    assert run.saved_step == last + 1
    m = res["metrics"]
    assert set(m) == {"train_tokens_per_s", "ckpt_durable_s", "setup_s"}
    steps = last - (TRAFFIC["iv"]["warmup_steps"] - 1)
    assert m["train_tokens_per_s"]["value"] == pytest.approx(
        steps * 2 * 64 / (run.t_close - run.t_open))
    assert 0 < m["ckpt_durable_s"]["value"] < run.t_close - run.t_open


def test_resume_counts_from_kill_to_first_step(smoke, monkeypatch):
    seen = {}
    orig = R.Run.end_to_end

    def spy(run):
        seen["run"] = run
        return orig(run)
    monkeypatch.setattr(R.Run, "end_to_end", spy)
    res = _run(smoke, "rs")
    run = seen["run"]
    assert res["correct"], res["checks"]
    assert len(run.cycles) >= 1 and res["attempted"] == len(run.cycles)
    for c in run.cycles + [run.check_cycle]:
        assert c["first_step"] == 6 and c["first_step_end"] > c["kill"]
        assert c["losses"] == run.continuation and len(c["losses"]) == 5
    assert run.check_cycle["kill"] > run.t_close
    want = np.mean([c["first_step_end"] - c["kill"] for c in run.cycles])
    assert res["metrics"]["resume_s"]["value"] == pytest.approx(want)
    assert run.feed.restored_steps == [6] * (len(run.cycles) + 1)
    assert res["checks"]["resume_restore_differ"]["value"] == 0


@pytest.mark.parametrize("leaf", ["m", "step"])
def test_wrong_full_restore_is_not_correct(smoke, monkeypatch, leaf):
    """A restore that is wrong the same way every time, on the full read
    path only (the sampled rows the durability check reads stay right):
    one leaf of Adam's state comes back stale."""
    from repro.checkpoint import manager as mg
    orig = mg.CheckpointManager.restore

    def restore(self, tree_like, step=None, slice_rows=None):
        out = orig(self, tree_like, step=step, slice_rows=slice_rows)
        if slice_rows is None:
            opt = dict(out["opt"])
            if leaf == "step":
                opt["step"] = np.asarray(opt["step"]) - 1
            else:
                opt["m"] = dict(opt["m"], embed=np.zeros_like(opt["m"]["embed"]))
            out = dict(out, opt=opt)
        return out
    monkeypatch.setattr(mg.CheckpointManager, "restore", restore)
    res = _run(smoke, "rs")
    assert not res["correct"]
    assert res["checks"]["resume_restore_differ"]["value"] > 0
    assert res["checks"]["resume_losses_differ"]["value"] > 0
    assert res["checks"]["ckpt_leaves_differ"]["value"] == 0


def test_traced_run_reports_per_layer_metrics(smoke):
    res = _run(smoke, "iv", trace=1)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert {"save_stall_s", "ckpt_write_mib_per_s", "nv_alloc_wait_share"} <= set(m)
    assert "train_tokens_per_s" not in m
    assert res["device"]["window_s"] > 0 and "breakdown" in res
    # the CPU has no device plane: no device metric is made up from it
    assert "device_idle_share" not in m and "step_mfu" not in m


def _fault(kind):
    import jax
    import jax.numpy as jnp
    from repro.train import steps as tsteps
    orig = tsteps.make_train_step

    def make(model, optimizer, *, compress=False):
        real = orig(model, optimizer, compress=compress)

        def step(state, batch):
            if kind == "half_batch":
                b = {"tokens": batch["tokens"][: batch["tokens"].shape[0] // 2]}
                return real(state, b)
            new, metrics = real(state, batch)
            if kind == "unchanged":
                return state, metrics
            return new, metrics
        return step
    return make


@pytest.mark.parametrize("kind", ["unchanged", "half_batch"])
def test_fault_under_timed_path_is_not_correct(smoke, monkeypatch, kind):
    from repro.train import steps as tsteps
    monkeypatch.setattr(tsteps, "make_train_step", _fault(kind))
    res = _run(smoke, "st", seconds=0.5)
    assert not res["correct"], res["checks"]


def test_altered_checkpoint_is_not_correct(smoke, monkeypatch):
    """An answer altered where it is produced: one leaf of the state is
    changed on its way into the checkpoint."""
    from repro.checkpoint import manager as mg
    orig = mg.CheckpointManager.save

    def save(self, step, tree):
        tree = dict(tree, opt=dict(tree["opt"], step=np.asarray(tree["opt"]["step"]) + 1))
        return orig(self, step, tree)
    monkeypatch.setattr(mg.CheckpointManager, "save", save)
    res = _run(smoke, "iv", seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["ckpt_leaves_differ"]["value"] > 0


ARCHS = {"mla_dense": ("minicpm3-4b", SMOKE_MODEL), "gqa_dense": ("minitron-8b", GQA_MODEL)}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_reference_starts_where_the_program_starts(name):
    """Every leaf of the program's initialisation, read through the
    reference's own leaf names, equals the reference's."""
    import jax
    from repro.configs.registry import get_smoke
    from repro.models import lm
    arch, model = ARCHS[name]
    ref = R.load_file(ROOT / f"bench/reference/{name}.py")
    key = jax.random.PRNGKey(R.fold_seed(SEED))
    p = ref.program_leaves(lm.init_lm(get_smoke(arch), key))
    q = ref.leaves(ref.init_params(model, key))
    assert set(p) == set(q)
    for k in q:
        np.testing.assert_array_equal(p[k], q[k], err_msg=k)


@pytest.mark.parametrize("name,seed", [("mla_dense", 3), ("mla_dense", 2**31 + 5),
                                       ("mla_dense", 2**40 + 9), ("gqa_dense", 11)])
def test_control_is_not_correct(name, seed):
    """The control, the float32 reference computed with every product in
    fp8, fails a limit that the program (bfloat16 products) meets, at the
    smoke size; read through the same state tap and gaps as a run."""
    import jax
    from repro.configs.registry import get_smoke
    from repro.models.registry import build
    from repro.optim.adamw import AdamW
    from repro.train import steps as tsteps
    from bench import check
    from bench.tokens import ZipfTokens
    opt_conf = json.loads((ROOT / "bench/configs/minicpm3-4b.nvmm64m.json")
                          .read_text())["optimizer"]
    arch, model_conf = ARCHS[name]
    ref = R.load_file(ROOT / f"bench/reference/{name}.py")
    feed = ZipfTokens(256, 2, 64, seed=seed, zipf_a=1.3)
    batches = [feed.batch_at(i)["tokens"] for i in range(check.CHECK_STEPS)]
    key = jax.random.PRNGKey(R.fold_seed(seed))
    f32 = ref.first_steps(model_conf, opt_conf, key, batches)
    fp8 = ref.first_steps(model_conf, opt_conf, key, batches, mm_dtype="float8_e4m3fn")
    model = build(get_smoke(arch))
    opt = AdamW(**opt_conf)
    state = tsteps.init_train_state(model, opt, key)
    step = jax.jit(tsteps.make_train_step(model, opt))
    tap, losses = check.StateTap(opt.b1, ref.program_leaves), []
    for i, b in enumerate(batches):
        state, m = step(state, {"tokens": b})
        losses.append(float(m["loss"]))
        tap(i, state)
    prog = check.gaps(check.program_readings(losses, tap, ref,
                                             ref.init_params(model_conf, key)), f32)
    control = check.gaps(fp8, f32)
    assert all(prog[k] <= v for k, v in SMOKE_LIMITS.items()), prog
    assert any(control[k] > v for k, v in SMOKE_LIMITS.items()), control


def test_no_chip_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(ROOT / "bench/run.py"), "--workload",
           "minicpm3-4b.nvmm64m.steady", "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_trace_reduction_on_small_trace():
    ms = 1e6
    tr = {"host": [("bench.window", 0, 100 * ms), ("bench.ckpt_save", 40 * ms, 90 * ms),
                   ("bench.step", 0, 30 * ms)],
          "ops": [("fusion.1", 5 * ms, 15 * ms), ("dot.2", 10 * ms, 25 * ms),
                  ("fusion.1", 95 * ms, 110 * ms), ("copy.3", -5 * ms, 2 * ms)],
          "modules": [("jit_step(7)", 5 * ms, 25 * ms), ("jit_step(7)", 95 * ms, 110 * ms),
                      ("jit_other(1)", 30 * ms, 31 * ms)]}
    r = tracereduce.reduce(tr)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.002 + 0.020 + 0.005)   # unions, clipped
    assert r["idle_share"] == pytest.approx(1 - 0.027 / 0.1)
    assert r["step_count"] == 1 and r["step_device_s"] == pytest.approx(0.020)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.015)]
    assert r["idle_gaps"][0] == ["ckpt_save", pytest.approx(0.070)]
    assert r["idle_gaps"][1] == ["step", pytest.approx(0.003)]   # host in a step


def test_trace_load_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.ckpt_save"):
            time.sleep(0.01)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracereduce.load(str(tmp_path))
    names = {n for n, _s, _e in tr["host"]}
    assert {"bench.window", "bench.ckpt_save"} <= names
    r = tracereduce.reduce(tr)
    assert r["window_s"] >= 0.01


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_flops_at_most_xla_count(one_chip):
    """At the cells' shapes, compiled for a described v5e: the model FLOPs
    are at most XLA's count of the compiled step and within 5% of it (XLA
    also counts the masked attention blocks and elementwise work)."""
    import jax
    import jax.numpy as jnp
    from repro.models.registry import build
    from repro.optim.adamw import AdamW
    from repro.train import steps as tsteps
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    conf = json.loads((ROOT / "bench/configs/minicpm3-4b.nvmm64m.json").read_text())
    model = build(R.program_config(conf))
    ref = R.load_reference(conf)
    opt = AdamW(**conf["optimizer"])
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    st = jax.tree.map(sds, tsteps.abstract_train_state(model, opt))
    B, S = conf["job"]["batch"], conf["job"]["seq"]
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)}
    c = jax.jit(tsteps.make_train_step(model, opt)).lower(st, batch).compile()
    ca = c.cost_analysis()
    xla = (ca[0] if isinstance(ca, list) else ca)["flops"]
    ours = ref.step_flops(conf["model"], B, S)
    assert 0.95 * xla <= ours <= xla


def test_benchmark_file_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["paths"] == ["bench"]
    names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench/reference" / f"{conf['reference']}.py").exists()
        for k in c["reduced"]:
            assert k in conf["reduced"]
            assert k in conf["model"] or R.config_value(conf, k) is not None
    for w in bench["workloads"]:
        assert w["config"] in names
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").exists()
    for m in bench["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").exists()
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
