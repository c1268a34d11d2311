"""MultiCellTrainer: C cells per aggregation step through one fused
round core + one batched scheduling dispatch.

Key contracts:
  * num_cells=1 reproduces the standalone FederatedTrainer bitwise
    (history records AND final params), for both scheduler backends;
  * with full availability (no padding) every cell of a C>1 run is
    bitwise-identical to a standalone trainer with the same seed — the
    cell axis is rolled (lax.map) on CPU, so the compiled body IS the
    single-cell program;
  * exactly one solve_many dispatch per fault-free round;
  * the WHOLE C-cell round makes <= 3 device->host syncs (2 fault-free:
    core pull + finalize norms), independent of C;
  * the accelerator cell axis (vmap) matches the CPU scan axis to f32
    tolerance for both the round core and the finalize core;
  * C=8 cells compile the round core once and make one solve_many and
    a C=1 round's host syncs per round (what a CPU run can settle; the
    speed these counts buy is measured on the chip).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.paper_cnn import CNNConfig
from repro.data import (sort_and_partition, synthetic_image_dataset,
                        train_test_split)
from repro.fl import FederatedTrainer, FLConfig, MultiCellTrainer
from repro.models import build_model


@pytest.fixture(scope="module")
def micro_world():
    ds = synthetic_image_dataset(num_classes=2, num_per_class=40,
                                 image_size=8, seed=0)
    train, test = train_test_split(ds, seed=0)
    parts = sort_and_partition(train.labels, 8, 1,
                               np.random.default_rng(0))
    model = build_model(CNNConfig(name="micro-cnn", kind="paper_cnn",
                                  num_classes=2, image_size=8,
                                  dropout=False, width=0.25))
    return model, train, test, parts


def micro_cfg(cells=1, seed=0, backend="jax", avail=1.0, **kw):
    kw.setdefault("scheduler", "fedcgd-fscd")
    return FLConfig(num_devices=8, available_prob=avail, batch_size=2,
                    tau=1, scheduler_backend=backend, eval_every=0,
                    seed=seed, num_cells=cells, **kw)


def params_equal(a, b):
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# parity


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_c1_bitwise_parity(micro_world, backend):
    model, train, test, parts = micro_world
    cfg = micro_cfg(backend=backend, avail=0.7)
    ref = FederatedTrainer(model, train, test, parts, cfg)
    mc = MultiCellTrainer(model, train, test, parts, cfg)
    for j in range(5):
        rec_ref = ref.run_round(j)
        rec_mc, = mc.run_round(j)
        assert rec_ref == rec_mc
    assert params_equal(ref.params, mc.cells[0].params)


def test_cells_match_standalone_trainers(micro_world):
    # full availability -> no padding -> every cell's rolled-core body is
    # the standalone program, so C=3 must equal 3 standalone runs bitwise
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts, micro_cfg(cells=3))
    mc.run(4)
    for c in range(3):
        ref = FederatedTrainer(model, train, test, parts,
                               micro_cfg(seed=c))
        ref.run(4)
        assert [r[c] for r in mc.history] == ref.history
        assert params_equal(ref.params, mc.cells[c].params)


def test_cells_evolve_independently(micro_world):
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts, micro_cfg(cells=2))
    mc.run(3)
    # distinct seeds -> distinct channel draws, batches, trajectories
    assert not params_equal(mc.cells[0].params, mc.cells[1].params)
    losses = [[r["mean_local_loss"] for r in recs] for recs in mc.history]
    assert losses[0][0] != losses[0][1]
    # determinism: the same construction replays the same histories
    mc2 = MultiCellTrainer(model, train, test, parts, micro_cfg(cells=2))
    mc2.run(3)
    assert mc2.history == mc.history


def test_padding_with_partial_availability(micro_world):
    # cells draw different availability -> device counts differ -> the
    # batched core/solve run padded; the padded rows must never surface
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts,
                          micro_cfg(cells=3, avail=0.5))
    for recs in mc.run(4):
        for rec in recs:
            assert rec["num_scheduled"] <= rec["num_available"]
            assert rec["num_uploaded"] <= rec["num_available"]
            assert np.isfinite(rec["mean_local_loss"])


# ---------------------------------------------------------------------------
# dispatch accounting


def test_one_solve_many_per_round(micro_world, monkeypatch):
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts, micro_cfg(cells=4))
    from repro.core import scheduling as S
    calls = []
    real = S.solve_many
    monkeypatch.setattr(S, "solve_many",
                        lambda *a, **k: calls.append(len(a[0])) or
                        real(*a, **k))
    mc.run(3)
    assert mc.solve_many_calls == 3
    assert calls == [4, 4, 4]      # one batched dispatch of C problems


def test_host_sync_budget(micro_world):
    model, train, test, parts = micro_world
    mc = MultiCellTrainer(model, train, test, parts, micro_cfg(cells=2))
    mc.run(2)
    for cell in mc.cells:
        assert cell.last_round_host_syncs <= 3
    ref = FederatedTrainer(model, train, test, parts, micro_cfg())
    ref.run_round(0)
    assert ref.last_round_host_syncs <= 3


def test_trainer_host_syncs_constant_in_c(micro_world):
    """The batched phase engine's core contract: the WHOLE C-cell round
    makes <= 3 device->host syncs (2 fault-free: core pull + finalize
    norms), and the count does not grow with C."""
    model, train, test, parts = micro_world
    syncs = {}
    for C in (2, 4):
        mc = MultiCellTrainer(model, train, test, parts,
                              micro_cfg(cells=C))
        mc.run(2)
        assert mc.last_round_host_syncs <= 3
        syncs[C] = mc.last_round_host_syncs
    assert syncs[2] == syncs[4]


def test_vmap_scan_cell_axis_parity(micro_world):
    """The accelerator path (cell_axis="vmap") must agree with the CPU
    scan path to f32 tolerance — runnable on CPU, no accelerator needed
    (vmap lowers to batched ops everywhere; only the numerics can
    drift, by reassociated f32 reductions)."""
    import jax.numpy as jnp
    from repro.fl.client import make_round_core
    from repro.fl.server import make_finalize_core
    model, train, test, parts = micro_world
    tr = FederatedTrainer(model, train, test, parts, micro_cfg())
    prep = tr._prepare_round(0)
    p2 = jax.tree.map(lambda x: jnp.stack([x, x]), tr.params)
    b2 = jax.tree.map(lambda x: jnp.stack([x, x]), prep.batches)
    k2 = jnp.stack([prep.subkey, prep.subkey])
    outs = {}
    for axis in ("scan", "vmap"):
        core = make_round_core(tr._loss, tr._sigma_one, tr.cfg.eta,
                               tr.cfg.tau, cell_axis=axis)
        outs[axis] = core(p2, b2, k2)
    for a, b in zip(jax.tree.leaves(outs["scan"]),
                    jax.tree.leaves(outs["vmap"])):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=2e-5, atol=2e-6)

    dev_params_c, _, _, deltas_c, _, _ = outs["scan"]
    V = jax.tree.leaves(deltas_c)[0].shape[1]
    w2 = np.full((2, V), 1.0 / V, np.float32)
    act2 = np.ones(2, bool)
    fouts = {}
    for axis in ("scan", "vmap"):
        fin = make_finalize_core(tr.cfg.tau, tr.cfg.eta, cell_axis=axis)
        fouts[axis] = fin(p2, dev_params_c, deltas_c, w2, act2)
    for a, b in zip(jax.tree.leaves(fouts["scan"]),
                    jax.tree.leaves(fouts["vmap"])):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=2e-5, atol=2e-6)


def test_rejects_unbatchable_scheduler(micro_world):
    model, train, test, parts = micro_world
    with pytest.raises(ValueError, match="batched scheduler"):
        MultiCellTrainer(model, train, test, parts,
                         micro_cfg(cells=2, scheduler="random"))


def test_faulty_rounds_backfill_batched(micro_world):
    from repro.faults.config import FaultConfig
    model, train, test, parts = micro_world
    cfg = micro_cfg(cells=3, faults=FaultConfig(dropout_prob=0.4,
                                                backfill=True))
    mc = MultiCellTrainer(model, train, test, parts, cfg)
    rounds = 4
    mc.run(rounds)
    # at most one extra batched dispatch per round (the backfill pass)
    assert rounds <= mc.solve_many_calls <= 2 * rounds
    fails = sum(r["num_failed"] for recs in mc.history for r in recs)
    assert fails > 0        # the fault stream actually fired


# ---------------------------------------------------------------------------
# what makes C cells cheaper than C trainers (counts; a CPU run cannot
# settle speed)


def test_c8_multicell_3x_faster(micro_world):
    """C=8 cells compile the round core once, where 8 standalone
    FederatedTrainers compile 8 copies of it (full availability: every
    round has the same shapes)."""
    model, train, test, parts = micro_world
    C, R = 8, 3
    mc = MultiCellTrainer(model, train, test, parts, micro_cfg(cells=C))
    mc.run(R)
    cores = {id(cell._round_core) for cell in mc.cells}
    assert cores == {id(mc.cells[0]._round_core)}
    assert mc.cells[0]._round_core._cache_size() == 1

    seq = [FederatedTrainer(model, train, test, parts, micro_cfg(seed=c))
           for c in range(C)]
    for tr in seq:
        tr.run(R)
    assert len({id(tr._round_core) for tr in seq}) == C
    assert sum(tr._round_core._cache_size() for tr in seq) == C


def test_c8_steady_state_speedup(micro_world):
    """Once compiled, a fault-free C=8 round makes one solve_many
    dispatch and as many host syncs as a C=1 round, where 8 standalone
    trainers make 8 solves and 8x the syncs."""
    model, train, test, parts = micro_world
    C, R = 8, 4
    syncs = {}
    for cells in (1, C):
        mc = MultiCellTrainer(model, train, test, parts,
                              micro_cfg(cells=cells))
        per_round = []
        for j in range(R):
            mc.run_round(j)
            per_round.append(mc.last_round_host_syncs)
        assert mc.solve_many_calls == R
        syncs[cells] = per_round
    assert syncs[C] == syncs[1]
    assert max(syncs[C]) <= 3

    seq = [FederatedTrainer(model, train, test, parts, micro_cfg(seed=c))
           for c in range(C)]
    seq_syncs = 0
    for tr in seq:
        tr.run_round(0)
        seq_syncs += tr.last_round_host_syncs
    assert seq_syncs == C * syncs[1][0]
