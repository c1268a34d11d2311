"""Batched engine (``solve_many``) vs the per-problem numpy solvers,
plus small-V oracle checks of every heuristic against ``exhaustive``."""
import numpy as np
import pytest

from repro.core import scheduling as S

RNG_PROBLEMS = 24  # >= 20 randomized problems per V (acceptance bar)


def random_problem(rng, V, C=8, infeasible_frac=0.0):
    p_dev = rng.dirichlet(np.full(C, 0.4), size=V)
    min_bw = rng.uniform(0.4, 1.6, V)
    if infeasible_frac:
        bad = rng.random(V) < infeasible_frac
        min_bw[bad] = -1.0                       # deadline-infeasible
    return S.Problem(
        p_dev=p_dev, global_dist=rng.dirichlet(np.full(C, 3.0)),
        class_weights=rng.uniform(0.5, 1.5, C),
        sigma=float(rng.uniform(2.0, 6.0)), batch_size=32,
        min_bw=min_bw, total_bw=V * 0.5)


# ---------------------------------------------------------------------------
# small-V oracle: heuristics vs the exact optimum


@pytest.mark.parametrize("V", [6, 9, 12])
def test_heuristics_vs_exhaustive(V):
    rng = np.random.default_rng(V)
    for t in range(8):
        prob = random_problem(rng, V, C=5)
        opt = S.exhaustive(prob).objective
        for fn, bound in ((S.greedy_scheduling, 2.0), (S.fscd, 1.5),
                          (S.coordinate_descent, 2.0)):
            obj = fn(prob).objective
            assert obj >= opt - 1e-9, (fn.__name__, t)
            # loose approximation bound: heuristics stay within a small
            # constant factor of the optimum on these instances
            assert obj <= bound * opt + 1e-9, (fn.__name__, t, obj, opt)


# ---------------------------------------------------------------------------
# batched engine == numpy loop, bitwise masks


@pytest.mark.parametrize("algorithm", ["gs", "fscd"])
@pytest.mark.parametrize("V", [8, 16, 64])
def test_solve_many_matches_numpy(algorithm, V):
    rng = np.random.default_rng(1000 + V)
    probs = [random_problem(rng, V,
                            infeasible_frac=0.2 if t % 3 == 0 else 0.0)
             for t in range(RNG_PROBLEMS)]
    numpy_fn = {"gs": S.greedy_scheduling, "fscd": S.fscd}[algorithm]
    expect = [numpy_fn(p) for p in probs]
    got = S.solve_many(probs, algorithm, backend="jax")
    assert len(got) == len(expect)
    for t, (e, g) in enumerate(zip(expect, got)):
        assert np.array_equal(e.mask, g.mask), (algorithm, V, t)
        assert e.iterations == g.iterations, (algorithm, V, t)
        assert np.isclose(e.objective, g.objective, rtol=0, atol=1e-9)


def deployment_problem(rng, V, C, max_classes, b=32):
    """A P1 instance shaped like the paper deployment's: each device
    holds 1..max_classes classes, its distribution is a histogram of b
    sampled labels (multiples of 1/b), and the class weights are one
    G-hat.  Many candidate sets then tie in exact arithmetic."""
    p_dev = np.zeros((V, C))
    for v in range(V):
        cls = rng.choice(C, size=rng.integers(1, max_classes + 1),
                         replace=False)
        labels = rng.choice(cls, size=b)
        p_dev[v] = np.bincount(labels, minlength=C) / b
    counts = rng.integers(80, 120, C)
    return S.Problem(
        p_dev=p_dev, global_dist=counts / counts.sum(),
        class_weights=np.full(C, float(rng.uniform(0.5, 3.0))),
        sigma=float(rng.uniform(2.0, 6.0)), batch_size=b,
        min_bw=rng.uniform(0.4, 1.6, V), total_bw=V * 0.5)


@pytest.mark.parametrize("pallas", [False, True])
@pytest.mark.parametrize("algorithm", ["gs", "fscd"])
@pytest.mark.parametrize("V,C,max_classes", [(24, 10, 2), (40, 4, 1)])
def test_solve_many_matches_numpy_on_ties(V, C, max_classes, algorithm,
                                          pallas):
    """Exact-arithmetic ties are decided by ``argmin_tied`` in both
    backends, not by how each rounds.  One-class devices tie by the
    dozen, more than the f32 top K holds: the jax engine then
    re-evaluates every candidate."""
    rng = np.random.default_rng(77 + V)
    probs = [deployment_problem(rng, V, C, max_classes)
             for _ in range(RNG_PROBLEMS)]
    numpy_fn = {"gs": S.greedy_scheduling, "fscd": S.fscd}[algorithm]
    got = S.solve_many(probs, algorithm, backend="jax", pallas=pallas)
    for t, (p, g) in enumerate(zip(probs, got)):
        assert np.array_equal(numpy_fn(p).mask, g.mask), (algorithm, t)


def test_argmin_tied_takes_lowest_index_within_margin():
    w = np.array([3.0, 1.0 + 5e-13, 2.0, 1.0, 1.0 - 5e-13])
    assert S.argmin_tied(w) == 1
    assert S.argmin_tied(np.array([2.0, 1.0, 1.0 + 1e-9])) == 1
    assert S.argmin_tied(np.array([[np.inf, 4.0], [4.0, 5.0]])) == 1
    assert S.argmin_tied(np.full(3, np.inf)) == 0


def test_solve_many_numpy_backend_identity():
    rng = np.random.default_rng(5)
    probs = [random_problem(rng, 12) for _ in range(4)]
    for alg, fn in (("gs", S.greedy_scheduling), ("fscd", S.fscd)):
        got = S.solve_many(probs, alg, backend="numpy")
        for e, g in zip([fn(p) for p in probs], got):
            assert np.array_equal(e.mask, g.mask)


def test_solve_many_mixed_feasibility_and_edge_cases():
    rng = np.random.default_rng(9)
    # one fully infeasible problem in the batch -> empty mask, like numpy
    probs = [random_problem(rng, 10) for _ in range(3)]
    dead = random_problem(rng, 10)
    dead.min_bw[:] = -1.0
    probs.append(dead)
    for alg, fn in (("gs", S.greedy_scheduling), ("fscd", S.fscd)):
        got = S.solve_many(probs, alg)
        for e, g in zip([fn(p) for p in probs], got):
            assert np.array_equal(e.mask, g.mask)
    assert not got[-1].mask.any()


def test_solve_many_validates_inputs():
    rng = np.random.default_rng(2)
    assert S.solve_many([], "gs") == []
    with pytest.raises(ValueError):
        S.solve_many([random_problem(rng, 8)], "not-an-algorithm")
    with pytest.raises(ValueError):
        S.solve_many([random_problem(rng, 8)], "gs", backend="tpu-magic")
    with pytest.raises(ValueError):
        S.solve_many([random_problem(rng, 8), random_problem(rng, 12)], "gs")


def test_trainer_backend_knob_masks_identical():
    """FederatedTrainer(scheduler_backend='jax') schedules the exact
    masks of the numpy path, round for round."""
    import dataclasses

    from benchmarks.common import mini_fl_world
    from repro.fl.rounds import FLConfig, FederatedTrainer

    model, train, test, parts = mini_fl_world(V=10)
    histories = {}
    for backend in ("numpy", "jax"):
        cfg = FLConfig(num_devices=10, available_prob=0.6, batch_size=8,
                       tau=1, scheduler="fedcgd-fscd",
                       scheduler_backend=backend, seed=3, eval_every=0)
        tr = FederatedTrainer(model, train, test, parts, cfg)
        hist = tr.run(3)
        histories[backend] = [(r["num_scheduled"], r["wemd"]) for r in hist]
    assert histories["numpy"] == histories["jax"]
