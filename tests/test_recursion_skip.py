"""The staged driver's skip of the recursion (``resume.run_staged``).

When the instance that ``prep`` hands on holds no live link (every
valid element a self-loop, as on one PE after local contraction), the
driver goes from ``prep`` straight to ``post``. These tests pin that
the skip returns the bytes of the full recursion (``api._solve_sharded``
runs every level whatever the instance), that it engages exactly when
every list stays inside one PE's block, and that a solve restored at
the prep boundary decides alike. They also pin what the solve reports
of that count (``live_links``) and of the sub-stores the recursion
built (``sub_capacity``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.listrank import (FaultSpec, ListRankConfig, api, instances,
                                 rank_list_seq, rank_list_with_stats,
                                 sim_mesh)
from repro.core.listrank import resume as resume_lib
from repro.core.listrank import transport as transport_lib
from repro.launch.mesh import make_mesh
from repro.runtime.fault_tolerance import (Preempted, SolveSupervisor,
                                           SolveSupervisorConfig)


def mesh1():
    return make_mesh((1,), ("pe",))


def with_weights(succ, rank, dtype, seed):
    """The instance's unit int32 weights, or random float32 weights
    (0 on terminals, as the solver requires)."""
    if dtype == np.int32:
        return rank.astype(np.int32)
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.5, 2.0, succ.shape[0]).astype(np.float32)
    w[succ == np.arange(succ.shape[0])] = 0.0
    return w


def block_local_lists(p, m, num_lists, seed):
    """``num_lists`` random lists inside each PE's block of ``m``."""
    succ, rank = [], []
    for b in range(p):
        s, r = instances.gen_list(m, gamma=1.0, seed=seed + b,
                                  num_lists=num_lists)
        succ.append(s + b * m)
        rank.append(r)
    return (np.concatenate(succ).astype(np.int32),
            np.concatenate(rank).astype(np.int32))


def full_recursion(succ, rank, mesh, cfg, seed=0):
    """(succ, rank) of the monolithic program, which runs every
    recursion level on any instance."""
    n = succ.shape[0]
    _, mesh, pe_axes, plan, cfg, m = api._resolve(n, mesh, None, cfg, None)
    owners = np.arange(n) // m
    term_bound = int(np.bincount(owners[succ == np.arange(n)],
                                 minlength=plan.p).max())
    specs = api.build_specs(cfg, plan, m, n, term_bound)
    runner = api._jitted_solver(mesh, plan, cfg, specs, m)
    wdt = api.canonical_weight_dtype(rank.dtype)
    out = runner(
        transport_lib.put_sharded(mesh, pe_axes, jnp.asarray(succ, jnp.int32)),
        transport_lib.put_sharded(mesh, pe_axes, jnp.asarray(rank, wdt)),
        jnp.int32(seed))
    stats = {k: int(v) for k, v in out[2].items()}
    assert all(stats[k] == 0 for k in resume_lib.FATAL_KEYS)
    return np.asarray(out[0]), np.asarray(out[1])


def assert_bytes_equal(got, want):
    for a, b in zip(got, want):
        a = np.asarray(a)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def assert_matches_oracle(succ, rank, got):
    s_ref, r_ref = rank_list_seq(succ, rank)
    np.testing.assert_array_equal(np.asarray(got[0]), s_ref)
    if rank.dtype == np.int32:
        np.testing.assert_array_equal(np.asarray(got[1]), r_ref)
    else:
        np.testing.assert_allclose(np.asarray(got[1]), r_ref, rtol=1e-4,
                                   atol=1e-4)


@pytest.mark.parametrize("algorithm", ("srs", "doubling"))
@pytest.mark.parametrize("dtype", (np.int32, np.float32),
                         ids=("int32", "float32"))
@pytest.mark.parametrize("num_lists", (1, 7))
@pytest.mark.parametrize("gamma", (0.0, 1.0))
def test_one_pe_skip_is_bit_identical_to_full_recursion(gamma, num_lists,
                                                        dtype, algorithm):
    succ, rank = instances.gen_list(512, gamma=gamma, seed=11,
                                    num_lists=num_lists)
    rank = with_weights(succ, rank, dtype, seed=12)
    cfg = ListRankConfig(algorithm=algorithm)
    sf, rf, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    assert stats["stage_log"] == ("prep", "post")
    assert stats["recursion_skipped"] == 1
    for key in ("rounds", "rulers", "sub_size", "pd_rounds", "chase_msgs",
                "max_queue"):
        assert stats[key] == 0
    full = full_recursion(succ, rank, mesh1(), cfg)
    assert_bytes_equal((sf, rf), full)
    assert_matches_oracle(succ, rank, full)


@pytest.mark.parametrize("algorithm", ("srs", "doubling"))
def test_multi_pe_random_list_runs_the_full_schedule(algorithm):
    succ, rank = instances.gen_list(1024, gamma=1.0, seed=3)
    cfg = ListRankConfig(algorithm=algorithm)
    sf, rf, stats = rank_list_with_stats(succ, rank, sim_mesh(4), cfg=cfg)
    labels = tuple(st.label for st in resume_lib.schedule_for(cfg))
    assert stats["stage_log"] == labels
    assert stats["recursion_skipped"] == 0
    assert_matches_oracle(succ, rank, (sf, rf))


@pytest.mark.parametrize("algorithm", ("srs", "doubling"))
def test_multi_pe_block_local_lists_skip(algorithm):
    succ, rank = block_local_lists(4, 256, num_lists=3, seed=21)
    cfg = ListRankConfig(algorithm=algorithm)
    sf, rf, stats = rank_list_with_stats(succ, rank, sim_mesh(4), cfg=cfg)
    assert stats["stage_log"] == ("prep", "post")
    assert stats["recursion_skipped"] == 1
    full = full_recursion(succ, rank, sim_mesh(4), cfg)
    assert_bytes_equal((sf, rf), full)
    assert_matches_oracle(succ, rank, full)


def test_no_live_link_skips_without_contraction():
    """Singletons leave nothing to rank with or without contraction;
    the rule reads the instance, not the configuration."""
    succ = np.arange(256, dtype=np.int32)
    rank = np.zeros(256, np.int32)
    cfg = ListRankConfig(local_contraction=False)
    sf, rf, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    assert stats["recursion_skipped"] == 1
    assert_bytes_equal((sf, rf), full_recursion(succ, rank, mesh1(), cfg))
    succ, rank = instances.gen_list(256, gamma=1.0, seed=4)
    _, _, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    assert stats["recursion_skipped"] == 0
    assert stats["stage_log"] == tuple(
        st.label for st in resume_lib.schedule_for(cfg))


@pytest.mark.parametrize("p,gamma", [(4, 0.0), (4, 1.0), (1, 0.0),
                                     (1, 1.0)])
def test_live_links_counts_what_contraction_leaves(p, gamma):
    """At gamma 0 each PE's block is one run of the list, so contraction
    leaves the p-1 links between blocks; at gamma 1 most links cross
    PEs; on one PE none is left and the recursion is skipped. Neither
    count costs a host sync: 54 on the full schedule, 29 on the skip."""
    succ, rank = instances.gen_list(2048, gamma=gamma, seed=8)
    cfg = ListRankConfig()
    tr = obs.Tracer()
    sf, rf, stats = rank_list_with_stats(succ, rank, sim_mesh(p), cfg=cfg,
                                         seed=3, tracer=tr)
    assert_matches_oracle(succ, rank, (sf, rf))
    (solve,) = tr.find(cat="solve")
    assert solve.args["live_links"] == stats["live_links"]
    assert solve.args["sub_capacity"] == stats["sub_capacity"]
    if p == 1:
        assert stats["recursion_skipped"] == 1
        assert stats["live_links"] == stats["sub_capacity"] == 0
        assert solve.args["host_syncs"] == 29
        return
    assert stats["recursion_skipped"] == 0
    assert solve.args["host_syncs"] == 54
    if gamma == 0.0:
        assert stats["live_links"] == p - 1
    else:
        assert stats["live_links"] > 100 * p
    _, _, _, plan, rcfg, m = api._resolve(2048, sim_mesh(p), None, cfg,
                                          None)
    specs = api.build_specs(rcfg, plan, m, 2048, term_bound=1)
    assert stats["sub_capacity"] == p * sum(
        specs[k].cap_sub for k in range(cfg.srs_rounds))
    assert stats["sub_capacity"] >= stats["sub_size"] > 0


def sup(tmp_path):
    return SolveSupervisor(SolveSupervisorConfig(
        ckpt_dir=str(tmp_path / "ckpt")))


@pytest.mark.parametrize("how", ("restart", "pe_loss", "preempt"))
def test_restored_solve_makes_the_same_skip(tmp_path, how):
    """A one-PE solve restored from its checkpoint skips as the fresh
    solve did, with the same bytes. ``restart`` and ``pe_loss`` restore
    the prep boundary (the skip writes no checkpoint of its own), so
    the decision is made again from the restored state; ``preempt``
    checkpoints the post boundary the skip went to."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=5, num_lists=3)
    cfg = ListRankConfig()
    fresh = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    assert fresh[2]["recursion_skipped"] == 1

    first = sup(tmp_path)
    if how == "preempt":
        with pytest.raises(Preempted):
            rank_list_with_stats(succ, rank, mesh1(), cfg=cfg,
                                 supervisor=first,
                                 inject=FaultSpec("preempt", stage="prep"))
        post_idx = len(resume_lib.schedule_for(cfg)) - 1
        assert first.latest_meta()["idx"] == post_idx
        assert first.latest_meta()["recursion_skipped"] == 1
        sf, rf, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg,
                                             supervisor=sup(tmp_path))
        assert stats["recovery"]["resumed_from"] == post_idx
        assert stats["stage_log"] == ("post",)
    elif how == "pe_loss":
        sf, rf, stats = rank_list_with_stats(
            succ, rank, mesh1(), cfg=cfg, supervisor=first,
            inject=FaultSpec("pe_loss", stage="post"))
        assert stats["recovery"]["resumed_from"] == 1
        assert stats["stage_log"] == ("prep", "post!InjectedFault", "post")
    else:
        rank_list_with_stats(succ, rank, mesh1(), cfg=cfg, supervisor=first)
        assert first.latest_meta()["idx"] == 1
        sf, rf, stats = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg,
                                             supervisor=sup(tmp_path))
        assert stats["recovery"]["resumed_from"] == 1
        assert stats["stage_log"] == ("post",)
    assert stats["recursion_skipped"] == 1
    assert_bytes_equal((sf, rf), [np.asarray(a) for a in fresh[:2]])


@pytest.mark.parametrize("srs_rounds", (0, 1))
def test_post_boundary_restore_of_a_full_solve(tmp_path, monkeypatch,
                                               srs_rounds):
    """A PE loss at ``post`` of a solve that runs the recursion restores
    the post boundary, whichever stage wrote it (``base@0`` when
    ``srs_rounds`` is 0): no prep-only key rides past the first stage
    after prep, so the restored state has the pytree the fresh solve
    handed ``post``."""
    succ, rank = instances.gen_list(512, gamma=1.0, seed=6)
    cfg = ListRankConfig(srs_rounds=srs_rounds, local_contraction=False)
    post_in = []                    # post's state pytree: fresh, restored
    stage_call = resume_lib._stage_call

    def spy(mesh, plan, cfg, stage, specs, m, state, *a):
        if stage.kind == "post":
            post_in.append(jax.tree.structure(state))
        return stage_call(mesh, plan, cfg, stage, specs, m, state, *a)

    monkeypatch.setattr(resume_lib, "_stage_call", spy)
    fresh = rank_list_with_stats(succ, rank, mesh1(), cfg=cfg)
    labels = tuple(st.label for st in resume_lib.schedule_for(cfg))
    assert fresh[2]["stage_log"] == labels
    sf, rf, stats = rank_list_with_stats(
        succ, rank, mesh1(), cfg=cfg, supervisor=sup(tmp_path),
        inject=FaultSpec("pe_loss", stage="post"))
    assert len(post_in) == 2 and post_in[0] == post_in[1]
    assert stats["recovery"]["resumed_from"] == len(labels) - 1
    assert stats["stage_log"] == labels[:-1] + ("post!InjectedFault",
                                                "post")
    assert stats["recursion_skipped"] == 0
    # the restored solve reports prep's count from the checkpoint
    assert stats["live_links"] == fresh[2]["live_links"] > 0
    assert stats["sub_capacity"] == fresh[2]["sub_capacity"]
    assert_bytes_equal((sf, rf), [np.asarray(a) for a in fresh[:2]])
    assert_matches_oracle(succ, rank, (sf, rf))
