"""Tests of the benchmark's own arithmetic and plumbing (CPU only).

  python3 -m pytest bench/test_bench.py

The last tests drive the rest of a run at a small size with the chip
check skipped: sound, it reads ``correct``; with each fault planted
underneath the timed path, and with the bfloat16 control in the
program's place, it does not.  Besides the benchmark's own cells they
drive a toy token configuration (``bench/toy``), added to a copy of the
benchmark as new files only."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TOY = BENCH / "toy"       # a token configuration's files, for these tests
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import jax  # noqa: E402

from harness import cell, check, data, flops, reference, spec, trace  # noqa: E402,E501


def _config(name):
    return spec.load_config(name)


# ---------------------------------------------------------------------------
# FLOPs and parameters


def test_paper_cnn_flops_by_hand():
    cfg, ref = _config("paper-cnn-cifar10")
    macs = [32 * 32 * 32 * 27,      # c1: 3x3x3 -> 32 at 32x32
            32 * 32 * 32 * 288,     # c2: 3x3x32 -> 32
            16 * 16 * 64 * 288,     # c3: 3x3x32 -> 64 at 16x16
            16 * 16 * 64 * 576,     # c4: 3x3x64 -> 64
            4096 * 120,             # fc1: 8x8x64 -> 120
            120 * 10]               # fc2
    layers = ref.layers(cfg)
    assert [flops.layer_macs(l) for l in layers] == macs
    assert flops.forward_flops(layers) == 2 * sum(macs) == 49_940_832
    # forward + weight gradient + input gradient of all but c1
    assert flops.train_flops(layers) == 3 * 49_940_832 - 2 * macs[0]
    assert flops.round_core_flops(layers, 1, 64, 1, 32) == \
        64 * 32 * (flops.train_flops(layers) + 49_940_832)


def test_resnet18_flops_by_hand():
    cfg, ref = _config("resnet18-gn-cifar100")
    c3 = lambda hw, cin, cout: hw * hw * cout * 9 * cin
    stage1 = 4 * c3(32, 64, 64)
    later = lambda hw, cin, cout: (c3(hw, cin, cout) + hw * hw * cout * cin
                                   + 3 * c3(hw, cout, cout))
    macs = (c3(32, 3, 64) + stage1 + later(16, 64, 128)
            + later(8, 128, 256) + later(4, 256, 512) + 512 * 100)
    assert macs == 555_468_800
    assert flops.forward_flops(ref.layers(cfg)) == 2 * macs


@pytest.mark.parametrize("name,count", [("paper-cnn-cifar10", 558_226),
                                        ("resnet18-gn-cifar100",
                                         11_220_132)])
def test_parameter_counts(name, count):
    cfg, ref = _config(name)
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == count
    assert cfg["parameters"] == count
    prog = jax.eval_shape(ref.program_model(cfg).init, jax.random.key(0))
    assert jax.tree.structure(prog) == jax.tree.structure(shapes)


def test_round_core_bytes():
    assert flops.round_core_bytes(10, 2, 3, 4, 5) == 2 * 10 * 4 \
        + 2 * 3 * 4 * 5 + 2 * 3 * 10 * 4


def test_toy_lm_counts_by_hand():
    cfg, ref = spec.load_config("toy-lm", TOY)
    traffic = spec.load_traffic("toy-lm-c1-v4", TOY)
    # d 32, 4 heads and 2 KV heads of 8, d_ff 64, vocab 64, S 16, 2 layers
    per_token = 2 * (32 * 8 * 8 + 4 * 8 * 32 + 3 * 32 * 64) + 32 * 64
    scores = 2 * 2 * 2 * 16 * 16 * 4 * 8
    fwd = 2 * 16 * per_token + scores
    params = 2 * (2 * 32 + 32 * 8 * 8 + 4 * 8 * 32 + 3 * 32 * 64) \
        + 2 * 64 * 32 + 32
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == params
    # 4 devices of 4 sequences: one SGD step (3 passes) and the sigma's
    # forward; the model read, the tokens read, 4 updates written
    assert flops.round_core(ref, cfg, traffic) == (
        16 * 4 * fwd, params * 4 + 16 * 16 * 4 + 4 * params * 4)


# the classifier's defaults restated through the four hooks
HOOKS = '''
from harness import flops as _flops


def loss(p, cfg, x, y, precision, key):
    _, logits = features_logits(p, cfg, x, precision, key)
    lse = jax.nn.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - true)


def sigma(p, cfg, x, y, precision):
    h, z = features_logits(p, cfg, x, precision)
    e = jax.nn.softmax(z, axis=-1) - jax.nn.one_hot(y, z.shape[-1],
                                                    dtype=z.dtype)
    gi = h[:, :, None] * e[:, None, :]
    dev_sq = jnp.sum(jnp.square(gi - gi.mean(0)), axis=(1, 2))
    return jnp.sqrt(jnp.mean(dev_sq))


def round_core_flops(cfg, t):
    return _flops.round_core_flops(layers(cfg), t["num_cells"],
                                   t["num_devices"], t["tau"],
                                   t["batch_size"])


def round_core_bytes(cfg, t):
    return _flops.round_core_bytes(cfg["parameters"], t["num_cells"],
                                   t["num_devices"], t["batch_size"],
                                   cfg["image_size"] ** 2
                                   * cfg["channels"] * 4)
'''


def _hooked(tmp_path, name):
    """The configuration's module with ``HOOKS`` appended."""
    d = tmp_path / "configs"
    d.mkdir(exist_ok=True)
    shutil.copy(BENCH / "configs" / f"{name}.json", d)
    (d / f"{name}.py").write_text(
        (BENCH / "configs" / f"{name}.py").read_text() + HOOKS)
    return spec.load_config(name, tmp_path)


@pytest.mark.parametrize("name,workload",
                         [("paper-cnn-cifar10", "cnn-c1-v64"),
                          ("resnet18-gn-cifar100", "resnet18-c1-v20")])
def test_hooks_restating_the_defaults_count_the_same(tmp_path, name,
                                                     workload):
    cfg, plain = _config(name)
    _, hooked = _hooked(tmp_path, name)
    w = spec.find_workload(spec.load_benchmark(), workload)
    traffic = spec.load_traffic(w["traffic"])
    assert flops.round_core(hooked, cfg, traffic) == \
        flops.round_core(plain, cfg, traffic)
    progs = [["jit_one_cell(3)", 0, 45_550_000]]
    for m in ("core_roofline", "round_mfu"):
        read = spec.load_module(BENCH / "metrics" / f"{m}.py", m).read
        got = [read(cell.Context(
            cfg=cfg, ref=ref, traffic=traffic, rounds=345, window_s=30.01,
            device={"platform": "tpu", "kind": "TPU v5 lite"},
            trace={"device_programs": progs})) for ref in (plain, hooked)]
        assert got[0] is not None and got[0] == got[1]


def test_hooks_restating_the_defaults_follow_the_same(tmp_path):
    cfg, plain = _config("paper-cnn-cifar10")
    _, hooked = _hooked(tmp_path, "paper-cnn-cifar10")
    x, y = data.images(5, 64, 10)
    w0 = jax.device_get(plain.init(jax.random.key(5), cfg))
    rng = np.random.default_rng(5)
    keys = reference.device_keys(5, [4, 4])
    rounds = [{"takes": rng.choice(64, (4, 4)), "keys": k,
               "upload": np.array([1, 0, 1, 1], bool)} for k in keys]
    a, b = (reference.follow(ref, cfg, w0, x, y, rounds, 0.01, chunk=2)
            for ref in (plain, hooked))
    for ra, rb in zip(a, b):
        assert (ra["loss"], ra["sigma_hat"]) == (rb["loss"], rb["sigma_hat"])
        np.testing.assert_array_equal(ra["dev_losses"], rb["dev_losses"])
        for pa, pb in zip(jax.tree.leaves(ra["params"]),
                          jax.tree.leaves(rb["params"])):
            np.testing.assert_array_equal(pa, pb)


def test_toy_lm_reference_follows_the_program():
    """The toy's reference loss and sigma against the program's model on
    the same weights and tokens (the program's own batch layout)."""
    from repro.core.estimation import sigma_hat_lastlayer
    cfg, ref = spec.load_config("toy-lm", TOY)
    w = jax.jit(lambda k: ref.init(k, cfg))(jax.random.key(3))
    model = ref.program_model(cfg)
    assert jax.tree.structure(jax.eval_shape(model.init, jax.random.key(0))) \
        == jax.tree.structure(w)
    x, y = data.tokens(3, 8, 4, cfg["seq_len"], cfg["vocab_size"], 1.1)
    toks = jax.numpy.asarray(x)
    batch = {"tokens": toks,
             "targets": jax.numpy.concatenate([toks[:, 1:], toks[:, -1:]], 1),
             "loss_mask": jax.numpy.ones(x.shape).at[:, -1].set(0.0)}
    hi = jax.lax.Precision.HIGHEST
    with jax.default_matmul_precision("highest"):
        prog_loss = float(model.loss_fn(w, batch)[0])
        logits = model.forward(w, batch)[0][:, -1]
        prog_sigma = float(sigma_hat_lastlayer(
            jax.numpy.ones((8, 1)), logits, batch["targets"][:, -1]))
    assert float(ref.loss(w, cfg, toks, y, hi, None)) == \
        pytest.approx(prog_loss, rel=1e-5)
    assert float(ref.sigma(w, cfg, toks, y, hi)) == \
        pytest.approx(prog_sigma, rel=1e-5)


# ---------------------------------------------------------------------------
# trace reduction on a small recorded trace

SMALL_TRACE = {
    # two rounds, 0-100 and 100-200 ns; ops overlap inside the first
    "steps": [[0, 0, 100], [1, 100, 200]],
    "device_ops": [["fusion.1", 10, 30], ["convolution.2", 20, 20],
                   ["fusion.1", 60, 10], ["fusion.3", 150, 40],
                   ["copy.4", 195, 20]],
    "device_programs": [["jit_one_cell(7)", 10, 30],
                        ["jit__lambda(8)", 60, 10],
                        ["jit__lambda(8)", 150, 50]],
}


def test_busy_union_and_idle_share():
    ops, (lo, hi) = SMALL_TRACE["device_ops"], trace.window(SMALL_TRACE)
    assert (lo, hi) == (0, 200)
    # copy.4 runs past the window's end and is clipped to it
    assert trace.busy_intervals(ops, lo, hi) == [[10, 40], [60, 70],
                                                 [150, 190], [195, 200]]
    assert trace.busy_ns(ops, lo, hi) == 85
    assert trace.idle_share(ops, lo, hi) == pytest.approx(0.575)
    assert trace.gaps(ops, lo, hi) == [(0, 10), (40, 60), (70, 150),
                                       (190, 195)]


def test_program_time_and_top_ops():
    assert trace.program_time(SMALL_TRACE["device_programs"],
                              r"^jit_one_cell\b") == (30.0, 1)
    assert trace.program_time(SMALL_TRACE["device_programs"],
                              r"^jit__lambda\b") == (60.0, 2)
    top = trace.top_ops(SMALL_TRACE["device_ops"], 2)
    assert top == [["fusion.1", 40e-9], ["fusion.3", 40e-9]]


def test_gaps_labelled_by_round_and_programs():
    g = trace.top_gaps(SMALL_TRACE, 3)
    assert g[0] == ["round 1: jit__lambda -> jit__lambda",
                    pytest.approx(80e-9)]
    assert g[1] == ["round 0: jit_one_cell -> jit__lambda",
                    pytest.approx(20e-9)]
    assert g[2] == ["round 0: start -> jit_one_cell", pytest.approx(10e-9)]
    inner = dict(SMALL_TRACE, device_programs=[["jit_x(1)", 0, 200]])
    assert trace.top_gaps(inner, 1)[0][0] == "round 1: inside jit_x"


def test_device_idle_share_reads_busy_time_against_the_window():
    read = spec.load_module(BENCH / "metrics" / "device_idle_share.py",
                            "idle").read
    # 85 ns busy over the 2 traced rounds; 10 untraced rounds in 1 us
    ctx = cell.Context(device={"platform": "cpu"}, trace=SMALL_TRACE,
                       rounds=10, window_s=1e-6)
    assert read(ctx) == pytest.approx(100 * (1 - 42.5e-9 * 10 / 1e-6))
    ctx.trace = dict(SMALL_TRACE, device_ops=[])
    assert read(ctx) is None


def test_op_and_program_names():
    hlo = ("%fusion.16 = (u32[1]{0:T(128)}) fusion(u32[2]{0:T(128)} "
           "%key.1), kind=kLoop")
    assert trace._op_name(hlo) == "fusion.16"
    progs = SMALL_TRACE["device_programs"]
    assert trace._program_at(progs, 30) == "jit_one_cell"
    assert trace._program_at(progs, 55) == "?"
    assert trace._program_at(progs, 160) == "jit__lambda"


# ---------------------------------------------------------------------------
# inputs from the seed


def test_images_deterministic_from_seed():
    big = 2 ** 33 + 5
    a = data.images(big, 64, 10, 32, 3)
    b = data.images(big, 64, 10, 32, 3)
    c = data.images(big + 1, 64, 10, 32, 3)
    assert a[0].shape == (64, 32, 32, 3) and a[0].dtype == np.float32
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert np.bincount(a[1], minlength=10).tolist() == [7] * 4 + [6] * 6


def test_tokens_deterministic_from_seed():
    big = 2 ** 33 + 5
    a = data.tokens(big, 400, 4, 64, 50, 1.1)
    b = data.tokens(big, 400, 4, 64, 50, 1.1)
    c = data.tokens(big + 1, 400, 4, 64, 50, 1.1)
    assert a[0].shape == (400, 64) and a[0].dtype == np.int32
    assert a[1].dtype == np.int32
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert np.bincount(a[1], minlength=4).tolist() == [100] * 4
    assert a[0].min() >= 0 and a[0].max() < 50
    # each topic's law: Zipf(1.1) over a ranking of its own
    law = np.arange(1, 51) ** -1.1
    law /= law.sum()
    freq = np.stack([np.bincount(a[0][a[1] == t].ravel(), minlength=50)
                     / 6400 for t in range(4)])
    for f in freq:
        np.testing.assert_allclose(np.sort(f)[::-1][:3], law[:3], atol=0.02)
    for i in range(4):
        for j in range(i):
            assert 0.5 * np.abs(freq[i] - freq[j]).sum() > 0.3


def test_freeze_keeps_lists_and_groups():
    cfg = {"a": 1, "l": [1, [2, 3]], "g": {"x": [4], "y": {"z": "w"}}}
    f = reference.freeze(cfg)
    assert f == {"a": 1, "l": (1, (2, 3)), "g": {"x": (4,), "y": {"z": "w"}}}
    assert isinstance(f["g"]["y"], dict)
    assert hash(f) == hash(reference.freeze(dict(reversed(cfg.items()))))
    assert hash(f) != hash(reference.freeze(dict(cfg, g={"x": [5]})))


def test_shards_deterministic_and_cover_every_row():
    labels = np.repeat(np.arange(10), 50)
    a = data.shards(labels, 8, 2, 3)
    assert [x.tolist() for x in a] == [x.tolist() for x in
                                       data.shards(labels, 8, 2, 3)]
    assert sorted(np.concatenate(a).tolist()) == list(range(500))


def test_device_keys_follow_the_programs_split_chain():
    k = jax.random.key(7)
    k, r0 = jax.random.split(k)
    _, r1 = jax.random.split(k)
    want = [jax.random.key_data(jax.random.split(r, n))
            for r, n in ((r0, 3), (r1, 5))]
    got = reference.device_keys(7, [3, 5])
    assert [g.shape for g in got] == [(3, 2), (5, 2)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, np.asarray(w))


def test_recording_array_logs_gathers_only_when_armed():
    x = np.arange(20.0).reshape(10, 2).view(data.RecordingArray)
    x[np.array([1, 2])]
    assert x.log is None
    x.log = []
    got = x[np.array([3, 1])]
    assert type(got) is np.ndarray and got.tolist() == [[6, 7], [2, 3]]
    assert [i.tolist() for i in x.log] == [[3, 1]]


# ---------------------------------------------------------------------------
# discovery by name


def test_files_found_by_name(tmp_path):
    bench = {"workloads": [{"name": "w1", "config": "cfg1",
                            "traffic": "t1", "chips": 1}],
             "end_to_end": [{"name": "rounds_per_s"},
                            {"name": "only_w2", "workloads": ["w2"]}],
             "per_layer": [{"name": "m1", "unit": "ms"},
                           {"name": "m2", "unit": "%",
                            "workloads": ["w2"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "cfg1.json").write_text('{"k": 1}')
    (b / "configs" / "cfg1.py").write_text("def layers(cfg): return cfg['k']")
    (b / "traffic" / "t1.json").write_text('{"num_cells": 3}')
    (b / "limits" / "w1.json").write_text('{"limits": {}}')
    (b / "metrics" / "m1.py").write_text("def read(ctx): return 4.5")
    loaded = spec.load_benchmark(tmp_path)
    w = spec.find_workload(loaded, "w1")
    cfg, ref = spec.load_config(w["config"], b)
    assert ref.layers(cfg) == 1
    assert spec.load_traffic("t1", b) == {"num_cells": 3}
    assert spec.load_limits("w1", b) == {"limits": {}}
    readers = spec.metric_readers(loaded, "w1", b)
    assert list(readers) == ["m1"] and readers["m1"][1](None) == 4.5
    assert [m["name"] for m in spec.end_to_end(loaded, "w1")] == \
        ["rounds_per_s"]
    with pytest.raises(spec.SpecError):
        spec.find_workload(loaded, "nope")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("missing", b)


def test_declared_files_exist():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        spec.load_config(w["config"])
        spec.load_traffic(w["traffic"])
        lim = spec.load_limits(w["name"])["limits"]
        assert set(lim) <= set(check.NUMBERS)
        assert "mask_mismatches" in lim
        assert len(spec.metric_readers(bench, w["name"])) \
            == len(bench["per_layer"])


# ---------------------------------------------------------------------------
# the measurement path refuses a device that is not a TPU


def test_device_check_refuses_cpu():
    with pytest.raises(cell.NoChip):
        cell.device_info(1, require_chip=True)
    assert cell.device_info(1, require_chip=False)["platform"] == "cpu"


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", "cnn-c1-v64", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "cnn-c1-v64", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ---------------------------------------------------------------------------
# a whole run at a small size, sound and with faults planted underneath

# each cell cut to a few devices and images (the models keep their widths)
SMALL = {
    "cnn-c1-v64": {
        "configs/paper-cnn-cifar10.json": {"train_images": 640,
                                           "test_images": 20},
        "traffic/c1-v64-full.json": {"num_devices": 4,
                                     "warm_fix_sums": [1, 4],
                                     "trace_rounds": 1}},
    # a deadline long enough that both devices can upload
    "resnet18-c1-v20": {
        "configs/resnet18-gn-cifar100.json": {"train_images": 400,
                                              "test_images": 100,
                                              "reference_chunk": 2},
        "traffic/c1-v20-full.json": {"num_devices": 2, "deadline_s": 60.0,
                                     "warm_fix_sums": [1, 2],
                                     "trace_rounds": 1}},
}


# the toy token cell: its files are new, its entries added to a copy
TOY_CELL = {"name": "toy-lm-c1-v4", "config": "toy-lm",
            "traffic": "toy-lm-c1-v4", "chips": 1}
CELLS = list(SMALL) + [TOY_CELL["name"]]


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A copy of the benchmark with every cell cut as ``SMALL`` says, and
    the toy token cell added to it as new files."""
    root = tmp_path_factory.mktemp("small")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-lm"})
    bench["workloads"].append(TOY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for files in SMALL.values():
        for rel, over in files.items():
            p = root / "bench" / rel
            p.write_text(json.dumps(dict(json.loads(p.read_text()), **over)))
    for f in TOY.rglob("*.*"):
        new = root / "bench" / f.relative_to(TOY)
        assert not new.exists(), new
        shutil.copy(f, new)
    return root


def _run(root, workload="cnn-c1-v64", fault=None, traced=False):
    return cell.run(workload, 2 ** 31 + 11, 0.2, traced,
                    time.perf_counter(), require_chip=False, fault=fault,
                    root=root, bench_dir=root / "bench", log=lambda m: None)


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr(cell, "enable_cache", lambda root=None: "off")


def test_sound_run_is_correct_and_prints_every_metric(small_root):
    r = _run(small_root)
    assert r["correct"], r["check"]
    assert set(r["metrics"]) == {"rounds_per_s", "setup_s"}
    assert list(r)[-1] == "check"
    assert r["check"]["mask_mismatches"]["value"] == 0


def test_toy_lm_sound_run_is_correct(small_root):
    r = _run(small_root, TOY_CELL["name"])
    assert r["correct"], r["check"]
    assert set(r["metrics"]) == {"rounds_per_s", "setup_s"}
    assert r["attempted"] > 0


def test_traced_run_reports_per_layer_metrics(small_root):
    r = _run(small_root, traced=True)
    assert r["correct"], r["check"]
    for name in ("prep_ms", "core_ms", "schedule_ms", "upload_ms",
                 "finalize_ms", "window_compiles"):
        assert name in r["metrics"]
    # the CPU has no device trace and no peaks: no share is made up
    for name in ("core_roofline", "round_mfu", "device_idle_share"):
        assert name not in r["metrics"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["frozen", "half_batch", "flip_mask"])
def test_planted_fault_is_not_correct(small_root, workload, fault):
    r = _run(small_root, workload, fault=fault)
    assert not r["correct"], r["check"]


# ---------------------------------------------------------------------------
# the control: the reference in bfloat16, put in the program's place


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(small_root, workload):
    import calibrate
    b = small_root / "bench"
    bench = spec.load_benchmark(small_root)
    w = spec.find_workload(bench, workload)
    cfg, ref = spec.load_config(w["config"], b)
    traffic = spec.load_traffic(w["traffic"], b)
    limits = spec.load_limits(w["name"], b)["limits"]
    prog, ctrl = calibrate.reading(cfg, ref, traffic, 2 ** 31 + 3,
                                   control=True)
    ctrl["mask_mismatches"] = 0
    assert check.judge(prog, limits), prog
    assert not check.judge(ctrl, limits), ctrl
