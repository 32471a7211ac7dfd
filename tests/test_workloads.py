"""Closed-form pins of the workload-lowering pass (repro.core.workloads).

Every byte count in a :class:`CommPlan` is closed-form, so these tests pin
them against independently computed figures from the model configs and the
sharding rules: DP all-reduce bytes equal parameter bytes at ``tp=1``, TP
collective ops move exactly ``tokens_per_rank * d_model`` activations, and
the MoE all-to-all demand matrix carries the padded-slot-tensor invariant
(row sums = ``bytes_per_rank * (ep-1)/ep``).  Parsing round-trips, the HLO
byte audit, placement strategies, and the Analysis/survey/fault_sweep wiring
are covered alongside.
"""
import numpy as np
import pytest

from repro.api import Analysis, WORKLOAD_COLUMNS, build, survey
from repro.configs.base import SHAPES, get_config
from repro.core import workloads as W
from repro.core.placement import place_ranks
from repro.models.moe import capacity

DENSE = "qwen2_7b"          # prefix of qwen2-7b (dense, 28 attn+mlp layers)
MOE = "grok_1_314b"         # prefix of grok-1-314b (8 experts, all-MoE)


# --------------------------------------------------------------------------
# spec parsing
# --------------------------------------------------------------------------

def test_parse_resolves_prefix_and_round_trips():
    ws = W.parse_workload(f"{MOE}@dp=8,tp=2,ep=4")
    assert ws.arch == "grok-1-314b"          # unique-prefix resolution
    assert (ws.dp, ws.tp, ws.ep) == (8, 2, 4)
    assert ws.world == 16
    assert W.parse_workload(ws.spec) == ws   # canonical string round-trips
    # passing a WorkloadSpec through is the identity
    assert W.parse_workload(ws) is ws


def test_parse_defaults_and_shape_key():
    ws = W.parse_workload(DENSE)
    assert (ws.dp, ws.tp, ws.ep, ws.shape) == (1, 1, 1, "train_4k")
    assert "shape=" not in ws.spec           # default shape omitted
    ws2 = W.parse_workload(f"{DENSE}@dp=2,shape=train_4k")
    assert ws2.shape == "train_4k"


@pytest.mark.parametrize("bad", [
    "no_such_model@dp=2",                    # unknown model
    "qwen2@dp=2",                            # ambiguous: qwen2-7b / qwen2-vl-7b
    f"{DENSE}@zz=3",                         # unknown key
    f"{DENSE}@dp",                           # missing =value
    f"{DENSE}@dp=x",                         # non-integer
    f"{DENSE}@dp=0",                         # < 1
    f"{DENSE}@dp=7",                         # 7 does not divide global_batch 256
    f"{DENSE}@dp=4,ep=2",                    # dense arch cannot take ep > 1
    f"{MOE}@dp=4,ep=8",                      # ep must divide dp
    f"{MOE}@dp=6,ep=3",                      # ep must divide n_experts (8)
    f"{DENSE}@shape=decode_32k",             # non-train shape
    f"{DENSE}@shape=nope",                   # unknown shape
])
def test_parse_rejects_invalid_specs(bad):
    with pytest.raises(W.WorkloadSpecError):
        W.parse_workload(bad)
    # WorkloadSpecError is a ValueError, so generic handlers still catch it
    with pytest.raises(ValueError):
        W.parse_workload(bad)


# --------------------------------------------------------------------------
# closed-form byte pins
# --------------------------------------------------------------------------

def test_dp_allreduce_bytes_equal_param_bytes_at_tp1():
    """With no tensor parallelism every gradient element is all-reduced, so
    the DP phase total must equal the parameter bytes exactly — and both must
    match the analytic ``param_count`` at the param dtype width."""
    plan = W.plan_workload(f"{DENSE}@dp=8")
    cfg = get_config(plan.spec.arch)
    assert plan.param_bytes == cfg.param_count() * 2          # bf16 params
    assert plan.grad_bytes_per_rank == pytest.approx(plan.param_bytes)
    ar = plan.phase("dp_allreduce")
    assert ar.total_bytes == pytest.approx(plan.grad_bytes_per_rank)
    assert ar.ops_per_step == int(np.ceil(plan.param_bytes / W.BUCKET_BYTES))
    assert ar.bytes_per_rank <= W.BUCKET_BYTES


def test_tp_shard_factor_shrinks_dp_bytes():
    """tp=2 halves every 'model'-sharded gradient; the DP total must drop
    strictly below the parameter bytes but stay above bytes/tp (norms and
    the router stay replicated)."""
    p1 = W.plan_workload(f"{DENSE}@dp=8")
    p2 = W.plan_workload(f"{DENSE}@dp=8,tp=2")
    assert p2.grad_bytes_per_rank < p1.grad_bytes_per_rank
    assert p2.grad_bytes_per_rank > p1.grad_bytes_per_rank / 2


def test_tp_phase_moves_full_activation_per_op():
    plan = W.plan_workload(f"{DENSE}@dp=4,tp=2")
    cfg = get_config(plan.spec.arch)
    shape = SHAPES["train_4k"]
    tokens_rank = shape.global_batch * shape.seq_len // 4
    assert plan.tokens_per_rank == tokens_rank
    ag = plan.phase("tp_allgather")
    rs = plan.phase("tp_reducescatter")
    # each op carries the full tokens x d_model activation in compute dtype
    assert ag.bytes_per_rank == tokens_rank * cfg.d_model * 2
    assert rs.bytes_per_rank == ag.bytes_per_rank
    # attn (wq/wo) + dense mlp (wg/wd) = 2 sharded pairs per layer, fwd+bwd
    assert ag.ops_per_step == 2 * (2 * cfg.n_layers)
    assert rs.ops_per_step == ag.ops_per_step


def test_moe_phase_matches_padded_slot_tensor():
    plan = W.plan_workload(f"{MOE}@dp=8,ep=4")
    cfg = get_config(plan.spec.arch)
    shape = SHAPES["train_4k"]
    E, k = cfg.n_experts, cfg.experts_per_token
    C = capacity(shape.seq_len, E, k, cfg.capacity_factor)
    groups_per_rank = shape.global_batch // 8
    slot_elems = groups_per_rank * E * C * cfg.d_model
    disp = plan.phase("moe_dispatch")
    comb = plan.phase("moe_combine")
    disp_width = W._DTYPE_BYTES[cfg.moe_dispatch_dtype]
    assert disp.bytes_per_rank == slot_elems * disp_width
    assert comb.bytes_per_rank == slot_elems * 2      # bf16 return legs
    moe_layers = sum(1 for s in cfg.pattern if s.moe) * cfg.n_repeats
    assert disp.ops_per_step == moe_layers
    assert comb.ops_per_step == 3 * moe_layers        # fwd return + 2 bwd legs


def test_dense_plan_has_no_moe_phase_and_dp1_no_allreduce():
    plan = W.plan_workload(f"{DENSE}@tp=2")
    names = [p.name for p in plan.phases]
    assert "dp_allreduce" not in names                # dp=1: nothing to reduce
    assert "moe_dispatch" not in names
    with pytest.raises(KeyError):
        plan.phase("moe_dispatch")


# --------------------------------------------------------------------------
# logical demand invariants
# --------------------------------------------------------------------------

def _demand(phase, groups, node_of, n):
    """(node-level demand matrix, round count) of one phase: its node pairs
    summed into an (n, n) matrix."""
    u, v, b, rounds = W._phase_pairs(phase, groups, node_of)
    D = np.zeros((n, n))
    np.add.at(D, (u, v), b)
    return D, rounds


def test_all_to_all_demand_row_sums_are_routed_fraction():
    """Each rank keeps 1/ep of its slot tensor local; the off-diagonal demand
    row must sum to exactly bytes_per_rank * (ep-1)/ep."""
    plan = W.plan_workload(f"{MOE}@dp=8,ep=4")
    phase = plan.phase("moe_dispatch")
    groups = W._phase_groups(plan, "ep")
    assert len(groups) == 2                           # (dp/ep) * tp groups
    node_of = np.arange(plan.world)                   # identity placement
    D, rounds = _demand(phase, groups, node_of, plan.world)
    want = phase.bytes_per_rank * (4 - 1) / 4
    np.testing.assert_allclose(D.sum(axis=1), want, rtol=1e-12)
    assert rounds == phase.ops_per_step               # a2a: one round per op


def test_ring_demand_rounds_and_per_edge_payload():
    plan = W.plan_workload(f"{DENSE}@dp=4,tp=2")
    ar = plan.phase("dp_allreduce")
    groups = W._phase_groups(plan, "dp")
    node_of = np.arange(plan.world)
    D, rounds = _demand(ar, groups, node_of, plan.world)
    # ring all-reduce: 2(g-1) rounds of 1/g payload along each group edge
    assert rounds == 2 * (4 - 1) * ar.ops_per_step
    np.testing.assert_allclose(D.sum(axis=1), ar.bytes_per_rank / 4,
                               rtol=1e-12)
    # DP groups stride by tp, so rank r talks to r +- tp, never r +- 1
    assert D[0, 1] == 0.0 and D[0, 2] > 0.0


def test_colocated_ranks_communicate_for_free():
    """Oversubscription folds whole TP groups onto one node under linear
    placement; their demand lands on the (zeroed) diagonal."""
    plan = W.plan_workload(f"{DENSE}@dp=4,tp=2")     # world 8
    node_of = place_ranks(4, plan.world, strategy="linear")   # 2 ranks/node
    tp = plan.phase("tp_allgather")
    D, _ = _demand(tp, W._phase_groups(plan, "tp"), node_of, 4)
    assert D.sum() == 0.0                             # every TP pair co-located


# --------------------------------------------------------------------------
# HLO byte audit
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [f"{DENSE}@dp=8,tp=2", f"{MOE}@dp=8,ep=4"])
def test_hlo_crosscheck_agrees(spec):
    check = W.hlo_crosscheck(spec)
    assert check["ok"], check
    kinds = check["kinds"]
    plan = W.plan_workload(spec)
    assert set(kinds) == set(plan.collective_byte_totals())
    for row in kinds.values():
        assert row["plan_bytes"] > 0


# --------------------------------------------------------------------------
# rank placement
# --------------------------------------------------------------------------

def test_place_ranks_strategies_and_balance():
    n, world = 8, 20
    for strategy in ("linear", "round_robin", "random"):
        nodes = place_ranks(n, world, strategy=strategy, seed=3)
        assert nodes.shape == (world,)
        loads = np.bincount(nodes, minlength=n)
        assert loads.max() - loads.min() <= 1         # balanced
    assert np.array_equal(place_ranks(n, world, strategy="round_robin"),
                          np.arange(world) % n)
    # random is a seeded relabeling: deterministic per seed, differs by seed
    r0 = place_ranks(n, world, strategy="random", seed=0)
    assert np.array_equal(r0, place_ranks(n, world, strategy="random", seed=0))
    assert any(not np.array_equal(r0, place_ranks(n, world, strategy="random",
                                                  seed=s)) for s in (1, 2, 3))


def test_place_ranks_rejects_bad_arguments():
    with pytest.raises(ValueError):
        place_ranks(0, 4)
    with pytest.raises(ValueError):
        place_ranks(4, 0)
    with pytest.raises(ValueError):
        place_ranks(4, 8, strategy="nope")


# --------------------------------------------------------------------------
# execution + API wiring
# --------------------------------------------------------------------------

def test_simulate_workload_composition():
    topo = build("hypercube(3)")                      # n = 8 = world
    res = W.simulate_workload(topo, f"{DENSE}@dp=4,tp=2", placement="random",
                              seed=1)
    assert res.n == 8 and res.plan.world == 8
    # step composition: compute + tp + moe + exposed dp, all non-negative
    want = (res.compute_seconds + res.tp_seconds + res.moe_seconds
            + res.exposed_dp_seconds)
    assert res.step_seconds == pytest.approx(want)
    assert res.exposed_dp_seconds <= res.dp_seconds
    assert 0.0 <= res.exposed_comm_fraction < 1.0
    assert res.dropped_frac == 0.0                    # hypercube is connected
    assert set(res.phase_seconds()) == {p.name for p in res.plan.phases}
    d = res.to_dict()
    assert d["step_ms"] == pytest.approx(res.step_seconds * 1e3, rel=1e-6)
    assert "step time" in res.report() and plan_text_ok(res.plan.report())


def plan_text_ok(text: str) -> bool:
    return "workload" in text and "compute/rank" in text


def test_analysis_simulate_workload_caches():
    a = Analysis("hypercube(3)")
    r1 = a.simulate(workload=f"{DENSE}@dp=4,tp=2", placement="linear")
    r2 = a.simulate(workload=f"{DENSE}@dp=4,tp=2", placement="linear")
    assert r1 is r2                                   # memoized per (spec, ...)
    r3 = a.simulate(workload=f"{DENSE}@dp=4,tp=2", placement="round_robin")
    assert r3 is not r1


def test_survey_appends_workload_columns():
    sr = survey(["hypercube(3)"], columns=["spec", "nodes", "rho2"],
                workload=f"{DENSE}@dp=4,tp=2")
    row = sr.rows[0]
    for col in WORKLOAD_COLUMNS:
        assert col in row, col
    assert row["workload"] == W.parse_workload(f"{DENSE}@dp=4,tp=2").spec
    assert row["step_time_ms"] > row["compute_ms"] > 0
    assert row["comm_total_ms"] == pytest.approx(
        row["comm_dp_ms"] + row["comm_tp_ms"] + row["comm_moe_ms"], rel=1e-6)


def test_fault_sweep_appends_workload_fields():
    a = Analysis("hypercube(3)")
    sweep = a.fault_sweep(rates=[0.05], samples=2,
                          workload=f"{DENSE}@dp=4,tp=2", workload_samples=1)
    row = sweep.rows[0]
    assert row["workload_step_mean"] > 0
    assert row["workload_step_max"] >= row["workload_step_mean"]
    assert 0.0 <= row["workload_dropped_frac_mean"] <= 1.0


# --------------------------------------------------------------------------
# spectral agreement statistic
# --------------------------------------------------------------------------

def test_spectral_rank_correlation_extremes_and_ties():
    perfect = [dict(rho2=r, step_ms=s) for r, s in
               [(4.0, 10.0), (3.0, 20.0), (2.0, 30.0), (1.0, 40.0)]]
    assert W.spectral_rank_correlation(perfect) == pytest.approx(1.0)
    reverse = [dict(rho2=r, step_ms=s) for r, s in
               [(4.0, 40.0), (3.0, 30.0), (2.0, 20.0), (1.0, 10.0)]]
    assert W.spectral_rank_correlation(reverse) == pytest.approx(-1.0)
    assert W.spectral_rank_correlation([dict(rho2=1.0, step_ms=1.0)]) is None
    assert W.spectral_rank_correlation(
        [dict(rho2=1.0, step_ms=None), dict(rho2=None, step_ms=2.0)]) is None
    # all-tied step times carry no ordering information
    tied = [dict(rho2=r, step_ms=5.0) for r in (3.0, 2.0, 1.0)]
    assert W.spectral_rank_correlation(tied) is None


# --------------------------------------------------------------------------
# DeepSeek-V3: the shape-only table, pipeline stages, split gradients
# --------------------------------------------------------------------------

DSV3 = "deepseek_v3@pp=4,dp=16,ep=4"        # 64 ranks, expert replicas 4


def test_deepseek_v3_table_matches_the_report():
    from repro.configs import deepseek_v3 as T

    assert T.param_count() == pytest.approx(671.03e9, rel=1e-3)
    assert T.active_param_count() == pytest.approx(37.6e9, rel=1e-2)
    assert T.param_count(mtp=True) - T.param_count() == \
        pytest.approx(11.6e9, rel=1e-2)
    dense, moe = T.layer_param_groups(0), T.layer_param_groups(3)
    assert set(dense) == set(T.GROUPS)
    assert dense["routed_experts"] == dense["router"] == 0
    assert dense["dense_ffn"] == 3 * 7168 * 18432
    assert moe["dense_ffn"] == 0
    assert moe["routed_experts"] == 256 * 3 * 7168 * 2048
    assert moe["shared_expert"] == 3 * 7168 * 2048
    assert moe["router"] == 256 * 7168
    with pytest.raises(ValueError):
        T.layer_param_groups(61)


def test_pp_grammar_round_trips_and_validates():
    ws = W.parse_workload(DSV3)
    assert ws.arch == "deepseek-v3"
    assert (ws.pp, ws.dp, ws.tp, ws.ep, ws.world) == (4, 16, 1, 4, 64)
    assert ws.spec.startswith("deepseek-v3@pp=4,dp=16")
    assert W.parse_workload(ws.spec) == ws
    assert "pp=" not in W.parse_workload(f"{DENSE}@dp=2").spec
    for bad in ("deepseek_v3@pp=62", "deepseek_v3@pp=0",
                "deepseek_v3@dp=4,tp=2", "deepseek_v3@dp=6,ep=3",
                f"{DENSE}@pp=29", "deepseek_v3@shape=nope"):
        with pytest.raises(W.WorkloadSpecError):
            W.parse_workload(bad)
    ws = W.parse_workload("deepseek_v3@pp=16,dp=256,ep=64,"
                          "shape=train_4k_gb15360")
    assert ws.world == 4096


def test_stage_layers_are_contiguous_and_even():
    assert W.stage_layers(61, 16) == [(4 * i, 4 * i + 4) for i in range(13)] \
        + [(52, 55), (55, 58), (58, 61)]
    assert [hi - lo for lo, hi in W.stage_layers(61, 4)] == [16, 15, 15, 15]
    assert W.stage_layers(28, 1) == [(0, 28)]


def test_deepseek_plan_closed_forms():
    from repro.configs import deepseek_v3 as T

    plan = W.plan_workload(DSV3, peak_flops=275e12)
    assert plan.stage_layers == (16, 15, 15, 15)
    assert {p.name for p in plan.phases} == {
        "dp_allreduce", "dp_allreduce_experts", "moe_dispatch",
        "moe_combine", "pp_sendrecv"}
    shape = SHAPES["train_4k"]
    tokens_rank = shape.global_batch * shape.seq_len // 16
    assert plan.tokens_per_rank == tokens_rank
    # pipeline: one sequence a micro-batch, bf16 activations each way
    pp = plan.phase("pp_sendrecv")
    assert (pp.collective, pp.group_axis, pp.group_size) == \
        ("send_recv", "pp", 4)
    assert pp.ops_per_step == shape.global_batch // 16
    assert pp.bytes_per_rank == shape.seq_len * 7168 * 2
    assert pp.total_bytes == tokens_rank * 7168 * 2
    # per stage: layers 0-15 (3 dense) + embedding; 16-30; 31-45; 46-60 +
    # head, final norm and the MTP layer
    ranges = [range(0, 16), range(16, 31), range(31, 46), range(46, 61)]
    mtp = T.mtp_param_groups()
    other = [sum(sum(T.layer_param_groups(i).values())
                 - T.layer_param_groups(i)["routed_experts"] for i in r)
             for r in ranges]
    experts = [sum(T.layer_param_groups(i)["routed_experts"] for i in r)
               for r in ranges]
    other[0] += T.embedding_params()
    other[3] += T.head_params() + sum(mtp.values()) - mtp["routed_experts"]
    experts[3] += mtp["routed_experts"]
    dp_ar = plan.phase("dp_allreduce")
    ex_ar = plan.phase("dp_allreduce_experts")
    assert (dp_ar.group_axis, dp_ar.group_size, dp_ar.n_groups) == \
        ("dp", 16, 4)
    assert (ex_ar.group_axis, ex_ar.group_size, ex_ar.n_groups) == \
        ("edp", 4, 16)
    for phase, totals in ((dp_ar, [2 * o for o in other]),
                          (ex_ar, [2 * e / 4 for e in experts])):
        assert phase.dtype == "bfloat16"
        assert phase.ops_per_step == max(
            int(np.ceil(t / W.BUCKET_BYTES)) for t in totals)
        np.testing.assert_allclose(
            np.array(phase.stage_bytes) * phase.ops_per_step, totals,
            rtol=1e-12)
        assert phase.bytes_per_rank == max(phase.stage_bytes)
    # dropless all-to-all: routed tokens, fp8 out, bf16 back; stage 0 has
    # 13 MoE layers, the last 15 + the MTP layer
    moe = [13, 15, 15, 16]
    disp, comb = plan.phase("moe_dispatch"), plan.phase("moe_combine")
    per_layer = tokens_rank * 8 * 7168
    assert (disp.dtype, comb.dtype) == ("float8_e4m3fn", "bfloat16")
    assert (disp.ops_per_step, comb.ops_per_step) == (16, 48)
    assert (disp.group_size, disp.n_groups) == (4, 16)
    np.testing.assert_allclose(np.array(disp.stage_bytes) * 16,
                               [m * per_layer for m in moe], rtol=1e-12)
    np.testing.assert_allclose(np.array(comb.stage_bytes) * 48,
                               [3 * m * per_layer * 2 for m in moe],
                               rtol=1e-12)
    # compute at the caller's peak, activated parameters with the MTP layer
    assert plan.compute_seconds == pytest.approx(
        6 * T.active_param_count(mtp=True) * shape.global_batch
        * shape.seq_len / 64 / 275e12)
    assert W.hlo_crosscheck(plan)["ok"]


def _old_dp_bytes(spec):
    """The DP all-reduce total before stages and split gradients: every
    gradient element over the DP group, after its TP shard."""
    ws = W.parse_workload(spec)
    cfg = get_config(ws.arch)
    return sum(int(np.prod(sh)) / W._model_shard_factor(ps, ws.tp)
               for _, sh, ps in W._iter_param_specs(ws)) \
        * W._DTYPE_BYTES[cfg.param_dtype]


@pytest.mark.parametrize("spec", [f"{DENSE}@dp=8", f"{DENSE}@dp=4,tp=2",
                                  f"{MOE}@dp=8", f"{MOE}@dp=8,tp=2",
                                  "jamba_v0_1_52b@dp=8,tp=2"])
def test_one_stage_plans_keep_their_bytes(spec):
    """Dense architectures, and MoE ones without expert parallelism, move
    exactly the bytes they moved before stages and split gradients."""
    plan = W.plan_workload(spec)
    ar = plan.phase("dp_allreduce")
    total = _old_dp_bytes(spec)
    n_buckets = max(1, int(np.ceil(total / W.BUCKET_BYTES)))
    assert ar.ops_per_step == n_buckets
    assert ar.bytes_per_rank == total / n_buckets
    assert ar.stage_bytes == ()
    assert plan.stage_layers == (get_config(plan.spec.arch).n_layers,)
    assert "dp_allreduce_experts" not in [p.name for p in plan.phases]


def test_capacity_moe_with_ep_splits_only_the_expert_gradient():
    """grok with ep=4: the expert gradient leaves the DP group for its
    dp/ep = 2 replicas; the rest, and the padded all-to-all, stay."""
    spec = f"{MOE}@dp=8,ep=4"
    plan = W.plan_workload(spec)
    cfg = get_config(plan.spec.arch)
    layers = sum(1 for s in cfg.pattern if s.moe) * cfg.n_repeats
    expert_bytes = layers * cfg.n_experts * 3 * cfg.d_model * cfg.moe_d_ff * 2
    ar, ex = plan.phase("dp_allreduce"), plan.phase("dp_allreduce_experts")
    assert ar.total_bytes == pytest.approx(_old_dp_bytes(spec) - expert_bytes,
                                           rel=1e-12)
    assert ex.total_bytes == pytest.approx(expert_bytes / 4, rel=1e-12)
    assert (ex.group_size, ex.n_groups) == (2, 4)
    groups = W._phase_groups(plan, "edp")
    assert [list(g) for _, g in groups] == [[e, e + 4] for e in range(4)]


def test_pod_placement_puts_stages_on_planes_and_ep_on_blocks():
    plan = W.plan_workload("deepseek_v3@pp=16,dp=256,ep=64,"
                           "shape=train_4k_gb15360")
    node = place_ranks(4096, plan.world, strategy="linear")
    x, y, z = node // 256, node // 16 % 16, node % 16
    for stage, ranks in W._phase_groups(plan, "dp"):
        assert set(x[ranks]) == {stage} and len(ranks) == 256
    for stage, ranks in W._phase_groups(plan, "ep"):
        ys = sorted(set(y[ranks]))
        assert set(x[ranks]) == {stage} and len(ranks) == 64
        assert len(ys) == 4 and ys[0] % 4 == 0 and ys == list(
            range(ys[0], ys[0] + 4)) and set(z[ranks]) == set(range(16))
    edges = {tuple(e) for e in np.sort(build("torus(16,3)").edges, axis=1)}
    for _, chain in W._phase_groups(plan, "pp"):
        a = node[chain]
        assert all(tuple(sorted((int(u), int(v)))) in edges
                   for u, v in zip(a[:-1], a[1:]))


def test_send_recv_demand_is_one_micro_batch_each_way():
    plan = W.plan_workload(DSV3)
    phase = plan.phase("pp_sendrecv")
    groups = W._phase_groups(plan, "pp")
    assert len(groups) == 16 and all(s == -1 for s, _ in groups)
    D, rounds = _demand(phase, groups, np.arange(64), 64)
    assert rounds == phase.ops_per_step
    b = phase.bytes_per_rank
    assert D[0, 16] == D[16, 0] == D[32, 48] == b
    assert D[0, 32] == 0.0
    np.testing.assert_allclose(D.sum(axis=1)[:16], b)        # first stage
    np.testing.assert_allclose(D.sum(axis=1)[16:48], 2 * b)  # middle stages


def test_fault_sweep_row_gives_each_phase_and_spans_name_the_steps():
    from repro import obs

    a = Analysis("torus(4,3)")
    before = obs.counters("workloads/")
    obs.reset_spans()
    obs.enable()
    try:
        sweep = a.fault_sweep(rates=[0.05], samples=2, workload=DSV3,
                              workload_samples=2)
    finally:
        obs.disable()
    row = sweep.rows[0]
    phases = row["workload_phase_seconds_mean"]
    assert set(phases) == {"pp_sendrecv", "moe_dispatch", "moe_combine",
                           "dp_allreduce", "dp_allreduce_experts"}
    assert all(v > 0 for v in phases.values())
    assert obs.counter_delta(before, "workloads/") == {
        "workloads/phases_lowered": 10}
    names = [e["name"] for e in obs.trace_events()]
    for name in ("workloads/demand", "workloads/lower", "workloads/engine"):
        assert names.count(name) == 10, name
    lower = [e for e in obs.trace_events() if e["name"] == "workloads/lower"]
    assert {e["args"]["phase"] for e in lower} == set(phases)
    assert all(e["args"]["rounds"] > 0 for e in lower)
    assert not any(n.startswith(("spectral/", "routing/analyze",
                                 "traffic/evaluate", "faults/sweep"))
                   for n in names if n.startswith("workloads/"))


@pytest.mark.parametrize("spec,rate", [(DSV3, 0.1), (DSV3, 0.7),
                                       (f"{MOE}@dp=16,tp=4,ep=8", 0.2)])
def test_pair_lowering_equals_the_dense_demand_lowering(spec, rate):
    """The phases' node pairs, routed by the traffic layer's router, give
    the slot loads of the dense demand matrix through the simulator's
    lowering bit for bit, and workloads its hops and dropped bytes (a
    shattered sample at 70% faults drops some)."""
    from repro.core import faults as F
    from repro.core.routing import analyze_routing
    from repro.core.simulate import (Schedule, _lower_demand_rounds,
                                     run_schedule)
    from repro.core.traffic import EcmpRouter

    g = build("torus(4,3)")
    g = F.apply_faults(g, F.random_link_faults(g, rate, seed=3))
    table = g.gather_operands()[0]
    plan = W.plan_workload(spec)
    routing = analyze_routing((table, g.n))
    node_of = place_ranks(g.n, plan.world, strategy="linear")
    router = EcmpRouter(table, routing.dist, routing.sigma, chunk=16)
    res = W.simulate_workload((table, g.n), plan, routing=routing, chunk=16)
    dropped_any = demand = 0.0
    for phase, row in zip(plan.phases, res.phase_rows):
        groups = W._phase_groups(plan, phase.group_axis)
        D, rounds = _demand(phase, groups, node_of, g.n)
        want, counts, hops, dropped = _lower_demand_rounds(
            table, routing, [(D, rounds, 1.0)], 16)
        u, v, b, got_rounds = W._phase_pairs(phase, groups, node_of)
        loads = router.pair_loads(u, v, b).astype(np.float32)
        assert got_rounds == rounds
        assert np.array_equal(loads, want[0]), phase.name
        # the workload's row: these loads, hops and rounds through the engine
        sched = Schedule(name="t", collective="c", algorithm="ecmp", n=g.n,
                         k=table.shape[1], round_bytes=want, counts=counts,
                         hops=hops, dropped_demand=dropped)
        assert row["max_link_bytes"] == float(want[0].max())
        assert row["seconds"] == float(
            run_schedule(sched, payloads=1.0).time_seconds[0]), phase.name
        dropped_any += dropped
        demand += rounds * float(D.sum() - np.trace(D))
    assert res.dropped_frac == pytest.approx(dropped_any / demand, rel=1e-12)
    assert (dropped_any > 0) == (rate > 0.5)


def test_pair_loads_need_an_all_sources_routing():
    """Pair demand indexes the routing by node, so a sampled routing is
    refused; its dense rows still route."""
    from repro.core.routing import analyze_routing
    from repro.core.traffic import EcmpRouter

    g = build("torus(4,3)")
    table = g.gather_operands()[0]
    routing = analyze_routing((table, g.n), sources=[0, 5, 9])
    router = EcmpRouter(table, routing.dist, routing.sigma)
    with pytest.raises(ValueError, match="all-sources"):
        router.pair_loads(np.array([0]), np.array([1]), np.array([1.0]))
    rows = np.zeros((3, g.n))
    rows[:, 1] = 1.0
    assert router.row_loads(rows).sum() == pytest.approx(
        float(routing.dist[:, 1].sum()))
