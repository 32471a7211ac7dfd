"""ML-workload lowering: model config + parallelism spec -> executed step time.

The paper's claim is that the spectral gap predicts interconnect performance;
:mod:`repro.core.simulate` (PR 5) executes synthetic collectives, but a real
training job is a *mix* of collectives with byte counts fixed by the model
architecture and the parallelism layout.  This module lowers an
architecture's parameter groups into that mix:

1. :func:`parse_workload` parses ``"kimi_k2_1t@dp=64,tp=8,ep=16"`` into a
   :class:`WorkloadSpec` — an architecture plus a (pipeline, data, tensor,
   expert)-parallel layout over ``world = pp * dp * tp`` ranks.
2. :func:`arch_params` reads the architecture through one interface,
   :class:`ArchParams`: per layer, the gradient elements a rank holds
   outside the routed experts and inside them, whether the layer routes
   through experts, and its tensor-parallel matmul pairs.  The seed LM stack
   feeds it from :func:`repro.models.model.param_shapes` and the *live*
   sharding rules (:func:`repro.parallel.sharding.param_pspecs`); a
   shape-only table (:data:`repro.configs.base.PARAM_TABLES`, DeepSeek-V3)
   feeds it from its published counts.
3. :func:`plan_workload` assigns the layers to pipeline stages and emits the
   per-training-step :class:`CommPlan`: one :class:`CommPhase` per
   collective stream, with closed-form byte counts —

   * **DP gradient all-reduce** (``dp_allreduce``) — a stage's non-expert
     gradient over its DP group, bucketized (:data:`BUCKET_BYTES`); at
     ``ep > 1`` the routed experts' gradient goes over the ``dp/ep``
     replicas of each expert shard instead (``dp_allreduce_experts``);
   * **TP all-gather / reduce-scatter per layer** — one pair per
     ``'model'``-sharded matmul pair found in the sharding rules (attention
     wq/wo, dense-MLP wg/wd, mamba in_proj/out_proj), moving the full
     activation ``tokens x d_model`` per direction (sequence-parallel
     lowering of the Megatron all-reduce), forward and backward;
   * **MoE all-to-all** — for an architecture with a capacity factor, the
     padded ``(E, C, D)`` slot-tensor exchange of
     :mod:`repro.parallel.ep_moe` (capacity ``C`` from
     :func:`repro.models.moe.capacity`); for a dropless one, the routed
     tokens ``tokens x experts_per_token x d_model``; dispatched in the
     architecture's dispatch dtype and returned/back-propagated in the
     compute dtype, over expert-parallel groups of size ``ep`` carved from
     the data axis;
   * **pipeline send/receive** (``pp_sendrecv``) — each stage's output
     activations to the next stage and their gradients back, one sequence a
     micro-batch.

4. :func:`simulate_workload` compiles the plan onto ANY topology: logical
   ranks map to physical nodes via :func:`repro.core.placement.place_ranks`,
   each phase lowers to a logical demand matrix (ring rounds for
   all-reduce/all-gather/reduce-scatter, the full pair demand for
   all-to-all, stage-to-stage pairs for send/receive), kept as node pairs,
   and the traffic layer's ECMP pass routes it onto the padded gather-table
   slots the round engine drains, over routing operands uploaded once a
   graph.
5. :func:`hlo_crosscheck` re-emits the plan as a synthetic post-partitioning
   HLO module (:meth:`CommPlan.to_hlo`) and checks the per-kind byte totals
   against the independent :func:`repro.launch.hlo_analysis.analyze_hlo`
   accounting.

Units: bytes for payloads, seconds for times, tokens = sequence positions.
Modeled: the phase families above, compute time from the 6*N*T FLOP
convention (the caller's chip peak, default
:data:`repro.launch.hlo_analysis.HW`), DP/backward overlap
(:data:`DP_OVERLAP_FRACTION`).  NOT modeled: embedding/loss collectives,
router aux losses, the pipeline bubble, HBM time (see docs/workloads.md).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro import obs

from .collectives import LINK_BW, PER_HOP_LATENCY
from .graphs import Topology
from .placement import place_ranks
from .routing import DEFAULT_SOURCE_CHUNK, RoutingResult, analyze_routing
from .simulate import Schedule, _unpack_topo, run_schedule
from .traffic import EcmpRouter

__all__ = [
    "WorkloadSpec", "WorkloadSpecError", "CommPhase", "CommPlan",
    "LayerParams", "ArchParams", "arch_params", "stage_layers",
    "WorkloadResult", "parse_workload", "plan_workload", "simulate_workload",
    "hlo_crosscheck", "spectral_rank_correlation", "BUCKET_BYTES",
    "DP_OVERLAP_FRACTION",
]

#: DP gradient all-reduce bucket size (bytes) — the plan splits the gradient
#: into ceil(total/BUCKET_BYTES) equal all-reduces, the standard overlap
#: granularity of data-parallel trainers.
BUCKET_BYTES = float(1 << 27)

#: fraction of the compute step the DP gradient all-reduce can hide behind
#: (the backward pass is ~2/3 of a fwd+bwd step at the 6*N*T FLOP convention,
#: and gradient buckets stream out as backward produces them).
DP_OVERLAP_FRACTION = 2.0 / 3.0

_DTYPE_BYTES = {
    "float32": 4, "bfloat16": 2, "float16": 2,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
}

#: jax dtype name -> HLO shape element type (repro.launch.hlo_analysis keys)
_HLO_DTYPE = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2",
}

#: phase collective -> HLO instruction mnemonic (analyze_hlo's accounting
#: keys: all-gather counts the gathered OUTPUT bytes, the rest sum operands)
_HLO_OP = {
    "all_reduce": "all-reduce", "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter", "all_to_all": "all-to-all",
    "send_recv": "collective-permute",
}

_DEFAULT_SHAPE = "train_4k"


class WorkloadSpecError(ValueError):
    """Malformed or inconsistent workload spec string."""


# --------------------------------------------------------------------------
# spec parsing
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """One parsed training-job layout: architecture + parallelism degrees.

    ``arch`` is the canonical registry name (a :mod:`repro.configs`
    architecture or a :data:`repro.configs.base.PARAM_TABLES` table);
    ``pp``/``dp``/``tp``/``ep`` are the pipeline-, data-, tensor- and
    expert-parallel degrees (``world = pp * dp * tp``; EP groups are
    size-``ep`` slices of each stage's data axis); ``shape`` names the
    training shape (:data:`repro.configs.base.SHAPES` or
    :data:`~repro.configs.base.WORKLOAD_SHAPES`).
    """
    arch: str
    dp: int = 1
    tp: int = 1
    ep: int = 1
    shape: str = _DEFAULT_SHAPE
    pp: int = 1

    @property
    def world(self) -> int:
        """Total rank count pp * dp * tp (EP reuses data-axis ranks)."""
        return self.pp * self.dp * self.tp

    @property
    def spec(self) -> str:
        """Canonical round-trippable spec string (``pp`` only when > 1)."""
        pp = f"pp={self.pp}," if self.pp != 1 else ""
        s = f"{self.arch}@{pp}dp={self.dp},tp={self.tp},ep={self.ep}"
        if self.shape != _DEFAULT_SHAPE:
            s += f",shape={self.shape}"
        return s


def _norm(name: str) -> str:
    return name.strip().lower().replace("-", "_").replace(".", "_")


def _resolve_arch(name: str) -> str:
    """Registry name from a normalized exact or unique-prefix match."""
    from repro.configs.base import PARAM_TABLES, list_configs

    want = _norm(name)
    if not want:
        raise WorkloadSpecError("workload spec needs a model name before '@'")
    names = sorted(list_configs() + list(PARAM_TABLES))
    exact = [c for c in names if _norm(c) == want]
    if exact:
        return exact[0]
    prefixed = [c for c in names if _norm(c).startswith(want)]
    if len(prefixed) == 1:
        return prefixed[0]
    if prefixed:
        raise WorkloadSpecError(
            f"ambiguous model name {name!r}: matches {prefixed}")
    raise WorkloadSpecError(
        f"unknown model {name!r}; registered configs: {names}")


def _positive_int(key: str, value: str) -> int:
    try:
        v = int(value)
    except ValueError:
        raise WorkloadSpecError(f"{key}= must be an integer, got {value!r}") \
            from None
    if v < 1:
        raise WorkloadSpecError(f"{key}= must be >= 1, got {v}")
    return v


def _shapes() -> Dict[str, Any]:
    from repro.configs.base import SHAPES, WORKLOAD_SHAPES

    return {**SHAPES, **WORKLOAD_SHAPES}


def parse_workload(spec: Union[str, "WorkloadSpec"]) -> "WorkloadSpec":
    """Parse ``"kimi_k2_1t@dp=64,tp=8,ep=16"`` into a :class:`WorkloadSpec`.

    Grammar: ``<model>[@<key>=<value>,...]`` with keys ``pp``/``dp``/``tp``/
    ``ep`` (positive ints, default 1) and ``shape`` (a ``kind="train"``
    entry of :data:`repro.configs.base.SHAPES` or
    :data:`~repro.configs.base.WORKLOAD_SHAPES`, default ``train_4k``).
    ``<model>`` is a registry name, matched case-insensitively with
    ``-``/``.``/``_`` interchangeable; a unique prefix (``kimi_k2_1t`` for
    ``kimi-k2-1t-a32b``) resolves too.

    Validated invariants (raising :class:`WorkloadSpecError`):
    ``global_batch % dp == 0``; ``pp <=`` the architecture's layer count;
    ``ep > 1`` needs an MoE arch with ``dp % ep == 0`` and
    ``n_experts % ep == 0``; a shape-only table has no tensor-parallel
    rules, so it takes ``tp == 1``.
    """
    if isinstance(spec, WorkloadSpec):
        return spec
    from repro.configs.base import PARAM_TABLES

    name, _, params = str(spec).partition("@")
    kv: Dict[str, Any] = dict(pp=1, dp=1, tp=1, ep=1, shape=_DEFAULT_SHAPE)
    if params.strip():
        for part in params.split(","):
            key, sep, value = part.partition("=")
            key = key.strip().lower()
            if not sep or not value.strip():
                raise WorkloadSpecError(
                    f"bad workload parameter {part!r} (expect key=value)")
            if key in ("pp", "dp", "tp", "ep"):
                kv[key] = _positive_int(key, value.strip())
            elif key == "shape":
                kv["shape"] = value.strip()
            else:
                raise WorkloadSpecError(
                    f"unknown workload key {key!r} "
                    "(known: pp, dp, tp, ep, shape)")
    arch = _resolve_arch(name)
    shapes = _shapes()
    if kv["shape"] not in shapes:
        raise WorkloadSpecError(
            f"unknown shape {kv['shape']!r} (known: {sorted(shapes)})")
    shape = shapes[kv["shape"]]
    if shape.kind != "train":
        raise WorkloadSpecError(
            f"workload shapes must be training shapes, {shape.name!r} is "
            f"kind={shape.kind!r}")
    if shape.global_batch % kv["dp"]:
        raise WorkloadSpecError(
            f"dp={kv['dp']} must divide global_batch={shape.global_batch} "
            f"of shape {shape.name!r}")
    n_layers, n_experts = _arch_extent(arch)
    if kv["pp"] > n_layers:
        raise WorkloadSpecError(
            f"pp={kv['pp']} exceeds the {n_layers} layers of {arch}")
    if arch in PARAM_TABLES and kv["tp"] > 1:
        raise WorkloadSpecError(
            f"{arch} is a shape-only table with no tensor-parallel rules; "
            f"tp={kv['tp']} needs an LM-stack architecture")
    if kv["ep"] > 1:
        if n_experts == 0:
            raise WorkloadSpecError(
                f"{arch} has no experts; ep={kv['ep']} needs an MoE arch")
        if kv["dp"] % kv["ep"]:
            raise WorkloadSpecError(
                f"ep={kv['ep']} must divide dp={kv['dp']} (EP groups are "
                "slices of the data axis)")
        if n_experts % kv["ep"]:
            raise WorkloadSpecError(
                f"ep={kv['ep']} must divide n_experts={n_experts}")
    return WorkloadSpec(arch=arch, pp=kv["pp"], dp=kv["dp"], tp=kv["tp"],
                        ep=kv["ep"], shape=kv["shape"])


def _arch_extent(arch: str) -> Tuple[int, int]:
    """(layers, routed experts) of a registry architecture or table."""
    from repro.configs.base import PARAM_TABLES, get_config

    if arch in PARAM_TABLES:
        table = _table_module(arch)
        return (table.CONFIG["num_hidden_layers"],
                table.CONFIG["n_routed_experts"])
    cfg = get_config(arch)
    return cfg.n_layers, cfg.n_experts


def _table_module(arch: str):
    import importlib

    from repro.configs.base import PARAM_TABLES

    return importlib.import_module(f"repro.configs.{PARAM_TABLES[arch]}")


# --------------------------------------------------------------------------
# sharding-rule consultation
# --------------------------------------------------------------------------

class _LogicalMesh:
    """Duck-typed stand-in for ``jax.sharding.Mesh`` carrying only the two
    attributes :mod:`repro.parallel.sharding` reads (``shape``,
    ``axis_names``) — so the workload planner consults the LIVE sharding
    rules without materializing dp*tp devices."""

    def __init__(self, dp: int, tp: int) -> None:
        self.axis_names = ("data", "model")
        self.shape = {"data": dp, "model": tp}


def _iter_param_specs(spec: WorkloadSpec
                      ) -> Iterator[Tuple[Tuple[Any, ...], Tuple[int, ...], Any]]:
    """Yield (path, shape, PartitionSpec) for every parameter leaf, pairing
    :func:`repro.models.model.param_shapes` with the PartitionSpecs that
    :func:`repro.parallel.sharding.param_pspecs` assigns on the logical
    (data=dp, model=tp) mesh.  ``path`` is the tuple of dict keys and list
    indices from the root (``("blocks", 0, "moe", "wg")``)."""
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.parallel import sharding

    cfg = get_config(spec.arch)
    mesh = _LogicalMesh(spec.dp, spec.tp)
    shapes = M.param_shapes(cfg)
    pspecs = sharding.param_pspecs(cfg, mesh)

    def walk(sh, ps, path=()):
        if isinstance(sh, M.Shape):
            yield path, tuple(sh), ps
        elif isinstance(sh, dict):
            for key in sh:
                yield from walk(sh[key], ps[key], path + (key,))
        else:  # list of pattern-position blocks
            for i, (s, p) in enumerate(zip(sh, ps)):
                yield from walk(s, p, path + (i,))

    yield from walk(shapes, pspecs)


def _model_shard_factor(pspec: Any, tp: int) -> int:
    """Product of 'model' mesh-axis sizes a PartitionSpec consumes (the
    tensor-parallel shard factor of that parameter)."""
    factor = 1
    for entry in tuple(pspec):
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            if ax == "model":
                factor *= tp
    return factor


def _has_model_axis(pspec: Any) -> bool:
    return _model_shard_factor(pspec, 2) > 1


# --------------------------------------------------------------------------
# parameter groups: the one interface the plan reads
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerParams:
    """Gradient elements of one layer (or of the embedding, the head, an MTP
    layer), as a rank of its stage holds them.

    ``dense`` counts every parameter outside the routed experts, after its
    tensor-parallel shard; ``experts`` the routed experts of the whole layer
    after their tensor-parallel shard (a rank holds ``1/ep`` of them);
    ``moe`` marks a layer that routes tokens through experts; ``tp_pairs``
    counts its ``'model'``-sharded matmul pairs.
    """
    dense: float
    experts: float = 0.0
    moe: bool = False
    tp_pairs: int = 0


@dataclasses.dataclass(frozen=True)
class ArchParams:
    """What :func:`plan_workload` reads of an architecture.

    ``layers`` are the model's layers in order, the unit pipeline stages
    take in contiguous runs; ``first`` sits on the first stage before them
    (the embedding), ``last`` on the last stage after them (final norm,
    head, multi-token-prediction layers).  ``capacity_factor`` is None for
    a dropless MoE (the all-to-all carries the routed tokens, not a padded
    slot tensor).
    """
    name: str
    d_model: int
    layers: Tuple[LayerParams, ...]
    first: Tuple[LayerParams, ...]
    last: Tuple[LayerParams, ...]
    param_count: int
    active_param_count: int
    n_experts: int
    experts_per_token: int
    capacity_factor: Optional[float]
    param_dtype: str
    compute_dtype: str
    moe_dispatch_dtype: str


def arch_params(spec: Union[str, WorkloadSpec]) -> ArchParams:
    """The :class:`ArchParams` of a workload's architecture at its layout
    (the tensor-parallel shard factors depend on ``tp``)."""
    from repro.configs.base import PARAM_TABLES

    ws = parse_workload(spec)
    if ws.arch in PARAM_TABLES:
        return _table_params(ws.arch)
    return _lm_params(ws)


def _table_params(arch: str) -> ArchParams:
    """A shape-only table's groups: the routed experts apart, every other
    group (attention, dense FFN, shared expert, router, norms, the MTP
    projection) in ``dense``; no tensor-parallel rules, so no pairs."""
    t = _table_module(arch)

    def layer(groups: Dict[str, int]) -> LayerParams:
        experts = groups["routed_experts"]
        return LayerParams(dense=float(sum(groups.values()) - experts),
                           experts=float(experts), moe=experts > 0)

    c = t.CONFIG
    mtp = tuple(layer(t.mtp_param_groups())
                for _ in range(c["num_nextn_predict_layers"]))
    return ArchParams(
        name=arch, d_model=c["hidden_size"],
        layers=tuple(layer(t.layer_param_groups(i))
                     for i in range(c["num_hidden_layers"])),
        first=(LayerParams(dense=float(t.embedding_params())),),
        last=(LayerParams(dense=float(t.head_params())),) + mtp,
        param_count=t.param_count(mtp=bool(mtp)),
        active_param_count=t.active_param_count(mtp=bool(mtp)),
        n_experts=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"], capacity_factor=None,
        param_dtype=t.GRAD_DTYPE, compute_dtype=t.COMPUTE_DTYPE,
        moe_dispatch_dtype=t.DISPATCH_DTYPE)


def _lm_params(ws: WorkloadSpec) -> ArchParams:
    """The seed LM stack's groups, read from the parameter shapes and the
    live sharding rules: layer ``r * len(pattern) + i`` is repeat r of
    pattern position i (blocks scan over repeats), and each block leaf's
    leading axis stacks the repeats."""
    from repro.configs.base import get_config
    from repro.parallel import sharding

    cfg = get_config(ws.arch)
    P = len(cfg.pattern)
    dense: List[float] = [0] * P
    experts: List[float] = [0] * P
    first: float = 0
    last: float = 0
    for path, sh, ps in _iter_param_specs(ws):
        factor = _model_shard_factor(ps, ws.tp)
        if path[0] == "blocks":
            i = path[1]
            numel = int(np.prod(sh[1:]))
            share = numel // factor if numel % factor == 0 else numel / factor
            if path[2] == "moe" and path[-1] != "router":
                experts[i] += share
            else:
                dense[i] += share
        else:
            numel = int(np.prod(sh))
            share = numel // factor if numel % factor == 0 else numel / factor
            if path[0] == "embed":
                first += share
            else:
                last += share
    pspecs = sharding.param_pspecs(cfg, _LogicalMesh(ws.dp, ws.tp))
    assert len(pspecs["blocks"]) == P
    pairs = []
    for blk in pspecs["blocks"]:
        n = 0
        if "attn" in blk and _has_model_axis(blk["attn"]["wq"]):
            n += 1
        if "mamba" in blk and _has_model_axis(blk["mamba"]["in_proj"]):
            n += 1
        if "mlp" in blk and _has_model_axis(blk["mlp"]["wg"]):
            n += 1
        pairs.append(n)
    pattern = [LayerParams(dense=dense[i], experts=experts[i],
                           moe=cfg.pattern[i].moe, tp_pairs=pairs[i])
               for i in range(P)]
    return ArchParams(
        name=ws.arch, d_model=cfg.d_model,
        layers=tuple(pattern[i] for _ in range(cfg.n_repeats)
                     for i in range(P)),
        first=(LayerParams(dense=first),), last=(LayerParams(dense=last),),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
        n_experts=cfg.n_experts, experts_per_token=cfg.experts_per_token,
        capacity_factor=cfg.capacity_factor, param_dtype=cfg.param_dtype,
        compute_dtype=cfg.compute_dtype,
        moe_dispatch_dtype=cfg.moe_dispatch_dtype)


def stage_layers(n_layers: int, pp: int) -> List[Tuple[int, int]]:
    """``[lo, hi)`` layer ranges of the ``pp`` stages: contiguous runs as
    even as ``n_layers`` allows, the first ``n_layers % pp`` stages one
    layer longer (61 layers over 16 stages: 13 stages of 4, then 3 of 3)."""
    q, r = divmod(n_layers, pp)
    out, lo = [], 0
    for s in range(pp):
        hi = lo + q + (1 if s < r else 0)
        out.append((lo, hi))
        lo = hi
    return out


# --------------------------------------------------------------------------
# the communication plan
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommPhase:
    """One collective stream of a training step.

    ``bytes_per_rank`` is the logical payload per op in the HLO accounting
    convention (all-reduce / reduce-scatter / all-to-all / send-receive:
    operand bytes; all-gather: gathered output bytes); ``ops_per_step``
    repeats it; ``group_axis`` in {"pp", "dp", "edp", "tp", "ep"} picks the
    rank grouping (group size ``group_size``, ``n_groups`` concurrent
    groups; "edp" are the replicas of one expert shard).  Stages run a
    phase together over ``ops_per_step`` ops, the largest stage's count;
    ``stage_bytes[p]`` is stage p's payload per op, its own total spread
    over those ops, and ``bytes_per_rank`` the largest of them (empty: every
    stage sends ``bytes_per_rank``).
    """
    name: str
    collective: str          # all_reduce | all_gather | reduce_scatter | all_to_all | send_recv
    group_axis: str          # pp | dp | edp | tp | ep
    group_size: int
    n_groups: int
    bytes_per_rank: float
    ops_per_step: int
    dtype: str
    note: str = ""
    stage_bytes: Tuple[float, ...] = ()

    @property
    def total_bytes(self) -> float:
        """Logical payload bytes per rank over the whole step (of the
        stage that sends most)."""
        return self.bytes_per_rank * self.ops_per_step

    def bytes_of(self, stage: int) -> float:
        """Payload per op of a rank of ``stage``."""
        return self.stage_bytes[stage] if self.stage_bytes \
            else self.bytes_per_rank


@dataclasses.dataclass(frozen=True)
class CommPlan:
    """The per-training-step communication plan of one workload.

    ``phases`` hold the closed-form byte counts; ``compute_seconds`` is the
    topology-independent FLOP term (6 * active params * tokens per rank at
    :data:`repro.launch.hlo_analysis.HW` peak).  Compile onto a topology with
    :func:`simulate_workload`; audit the byte accounting with
    :func:`hlo_crosscheck`.
    """
    spec: WorkloadSpec
    world: int
    tokens_per_step: int          # global tokens (batch * seq)
    tokens_per_rank: int          # per data shard
    param_bytes: float            # total parameter bytes (param_dtype)
    grad_bytes_per_rank: float    # DP all-reduce operand bytes per rank
    phases: Tuple[CommPhase, ...]
    flops_per_rank: float
    compute_seconds: float
    stage_layers: Tuple[int, ...] = ()   # main-model layers per stage
    peak_flops: float = 0.0               # chip peak the compute term used

    def phase(self, name: str) -> CommPhase:
        for p in self.phases:
            if p.name == name:
                return p
        raise KeyError(f"no phase {name!r} in plan (have: "
                       f"{[p.name for p in self.phases]})")

    def collective_byte_totals(self) -> Dict[str, float]:
        """Per-HLO-kind logical byte totals (the figures
        :func:`repro.launch.hlo_analysis.analyze_hlo` recovers from
        :meth:`to_hlo`)."""
        out: Dict[str, float] = {}
        for p in self.phases:
            kind = _HLO_OP[p.collective]
            out[kind] = out.get(kind, 0.0) + p.total_bytes
        return out

    def to_hlo(self) -> str:
        """Synthetic post-partitioning HLO text with one collective per phase
        (repeated ops as a while loop with ``known_trip_count``), shaped so
        the independent parser of :mod:`repro.launch.hlo_analysis` recovers
        exactly :meth:`collective_byte_totals`."""
        lines = [f"HloModule workload_{_norm(self.spec.arch)}", ""]
        entry: List[str] = []
        for i, p in enumerate(self.phases):
            dt = _HLO_DTYPE[p.dtype]
            numel = p.bytes_per_rank / _DTYPE_BYTES[p.dtype]
            trips = p.ops_per_step
            if abs(numel - round(numel)) > 1e-6:
                # bucketized phases can have fractional per-op element
                # counts; collapse to one instruction carrying the exact
                # phase total so the parsed bytes still match
                numel *= trips
                trips = 1
            numel = int(round(numel))
            shape = f"{dt}[{numel}]"
            op = _HLO_OP[p.collective]
            body = f"wl_body.{i}"
            cond = f"wl_cond.{i}"
            lines += [
                f"%{cond} (carg.{i}: {shape}) -> pred[] {{",
                f"  %clt.{i} = pred[] constant(false)",
                "}", "",
                f"%{body} (barg.{i}: {shape}) -> {shape} {{",
                f"  %arg.{i} = {shape} parameter(0)",
                f"  ROOT %coll.{i} = {shape} {op}(%arg.{i})",
                "}", "",
            ]
            entry.append(
                f"  %init.{i} = {shape} constant(0)")
            entry.append(
                f"  %while.{i} = {shape} while(%init.{i}), "
                f"condition=%{cond}, body=%{body}, backend_config="
                f'{{"known_trip_count":{{"n":"{trips}"}}}}')
        lines += ["ENTRY %main () -> f32[] {"] + entry + [
            "  ROOT %done = f32[] constant(0)", "}"]
        return "\n".join(lines)

    def report(self) -> str:
        """Compact text block for CLI reports."""
        s = self.spec
        lines = [
            f"workload        : {s.spec}",
            f"ranks           : {self.world} (pp={s.pp} x dp={s.dp} x "
            f"tp={s.tp}, ep={s.ep})",
            f"tokens/step     : {self.tokens_per_step:,} "
            f"({self.tokens_per_rank:,} per data shard)",
            f"compute/rank    : {self.flops_per_rank / 1e12:.1f} TFLOP "
            f"-> {self.compute_seconds * 1e3:.2f} ms at "
            f"{self.peak_flops / 1e12:.0f} TFLOP/s",
        ]
        if s.pp > 1:
            lines.append(f"stage layers    : {list(self.stage_layers)}")
        for p in self.phases:
            lines.append(
                f"  {p.name:<16}: {p.collective} x{p.ops_per_step} over "
                f"{p.group_axis} groups of {p.group_size}, "
                f"{p.bytes_per_rank / 1e6:.2f} MB/op ({p.dtype})")
        return "\n".join(lines)


@obs.traced("workloads/plan", phase="compile")
def plan_workload(spec: Union[str, WorkloadSpec],
                  peak_flops: Optional[float] = None) -> CommPlan:
    """Lower one workload spec into its per-step :class:`CommPlan`.

    Byte counts come from :func:`arch_params` (see the module docstring);
    every count is closed-form, so tests can pin them exactly.  Stage p
    holds the layers :func:`stage_layers` gives it, the embedding when
    ``p == 0`` and the head and MTP layers when ``p == pp - 1``.  Stages
    run a phase together over the largest stage's op count, each stage's
    own total spread evenly over those ops (a stage with fewer layers sends
    less a round); every count is an integer number of bytes, so one stage
    gets its per-op bytes exactly:

    * ``dp_allreduce`` total of a stage == its non-expert gradient bytes
      after the TP shard (all its gradient bytes at ``ep == 1``, so a
      one-stage dense plan moves the parameter bytes at ``tp == 1``);
      ``dp_allreduce_experts`` == its routed experts' bytes / ``ep``, over
      groups of ``dp / ep``;
    * each TP all-gather/reduce-scatter op moves ``tokens_per_rank *
      d_model`` activation elements;
    * each MoE all-to-all op moves the padded slot tensor
      ``groups_per_rank * E * capacity * d_model/tp`` elements, or for a
      dropless architecture ``tokens_per_rank * experts_per_token *
      d_model/tp``;
    * each ``pp_sendrecv`` op moves one micro-batch (a sequence) of
      activations, ``seq_len * d_model/tp`` elements, each way.

    ``peak_flops`` is the chip peak of the compute term (FLOP/s; default
    :data:`repro.launch.hlo_analysis.HW` ``["peak_flops"]``).
    """
    from repro.launch.hlo_analysis import HW
    from repro.models.moe import capacity

    ws = parse_workload(spec)
    ap = arch_params(ws)
    shape = _shapes()[ws.shape]
    pp, dp, tp, ep = ws.pp, ws.dp, ws.tp, ws.ep
    world = ws.world
    peak = float(HW["peak_flops"] if peak_flops is None else peak_flops)
    tokens = shape.global_batch * shape.seq_len
    tokens_rank = tokens // dp
    grad_bytes = _DTYPE_BYTES[ap.param_dtype]
    comp_bytes = _DTYPE_BYTES[ap.compute_dtype]
    d_share = ap.d_model // tp if ap.d_model % tp == 0 else ap.d_model

    ranges = stage_layers(len(ap.layers), pp)
    stages = [(ap.first if p == 0 else ()) + ap.layers[lo:hi]
              + (ap.last if p == pp - 1 else ()) for p, (lo, hi)
              in enumerate(ranges)]
    moe_layers = [sum(1 for g in st if g.moe) for st in stages]
    tp_pairs = [sum(g.tp_pairs for g in st) for st in stages]
    split = ep > 1
    dense = [sum(g.dense for g in st) + (0 if split else
                                         sum(g.experts for g in st))
             for st in stages]
    experts = [sum(g.experts for g in st) / ep if split else 0
               for st in stages]
    phases: List[CommPhase] = []

    def bucketized(name, axis, group, n_groups, elems, note):
        totals = [e * grad_bytes for e in elems]
        buckets = [max(1, int(math.ceil(t / BUCKET_BYTES))) for t in totals]
        ops = max(buckets)
        per = tuple(t / ops for t in totals)
        phases.append(CommPhase(
            name=name, collective="all_reduce", group_axis=axis,
            group_size=group, n_groups=n_groups, bytes_per_rank=max(per),
            ops_per_step=ops, dtype=ap.param_dtype,
            note=f"{note} bucketized x{ops} "
                 f"({BUCKET_BYTES / 1e6:.0f} MB buckets)",
            stage_bytes=per if pp > 1 else ()))

    # -- DP gradient all-reduce: a stage's gradient over its DP group -------
    if dp > 1:
        bucketized("dp_allreduce", "dp", dp, pp * tp, dense,
                   "non-expert gradient" if split else "gradient")
        if split and dp // ep > 1 and any(experts):
            bucketized("dp_allreduce_experts", "edp", dp // ep,
                       pp * tp * ep, experts,
                       f"expert gradient over {dp // ep} replicas,")

    # -- TP per-layer all-gather + reduce-scatter ---------------------------
    if tp > 1 and max(tp_pairs):
        act_bytes = float(tokens_rank) * ap.d_model * comp_bytes
        # fwd + bwd: 2 sequence-parallel all-reduces per pair, each lowered
        # as one all-gather + one reduce-scatter of the full activation
        ops = 2 * max(tp_pairs)
        per = tuple(2 * n * act_bytes / ops for n in tp_pairs)
        for name, coll in (("tp_allgather", "all_gather"),
                           ("tp_reducescatter", "reduce_scatter")):
            phases.append(CommPhase(
                name=name, collective=coll, group_axis="tp",
                group_size=tp, n_groups=pp * dp, bytes_per_rank=max(per),
                ops_per_step=ops, dtype=ap.compute_dtype,
                note=f"{sum(tp_pairs)} model-sharded matmul pairs",
                stage_bytes=per if pp > 1 else ()))

    # -- MoE all-to-all over EP groups --------------------------------------
    if ep > 1 and max(moe_layers):
        E, k = ap.n_experts, ap.experts_per_token
        if ap.capacity_factor is None:
            elems = float(tokens_rank) * k * d_share
            what = f"dropless, {k} experts a token"
        else:
            C = capacity(shape.seq_len, E, k, ap.capacity_factor)
            elems = float(shape.global_batch // dp * E * C) * d_share
            what = f"E={E} C={C} padded slots"
        for name, dtype, legs, leg_note in (
                ("moe_dispatch", ap.moe_dispatch_dtype, 1, "fwd dispatch"),
                ("moe_combine", ap.compute_dtype, 3,
                 "fwd return + bwd dispatch/return")):
            per_op = elems * _DTYPE_BYTES[dtype]
            ops = legs * max(moe_layers)
            per = tuple(legs * n * per_op / ops for n in moe_layers)
            phases.append(CommPhase(
                name=name, collective="all_to_all", group_axis="ep",
                group_size=ep, n_groups=pp * (dp // ep) * tp,
                bytes_per_rank=max(per), ops_per_step=ops, dtype=dtype,
                note=f"{what}, {leg_note}",
                stage_bytes=per if pp > 1 else ()))

    # -- pipeline: activations forward, their gradients back ----------------
    if pp > 1:
        micro = shape.global_batch // dp       # one sequence a micro-batch
        phases.append(CommPhase(
            name="pp_sendrecv", collective="send_recv", group_axis="pp",
            group_size=pp, n_groups=dp * tp,
            bytes_per_rank=float(shape.seq_len) * d_share * comp_bytes,
            ops_per_step=micro, dtype=ap.compute_dtype,
            note=f"{micro} micro-batches of one sequence, each way"))

    flops = 6.0 * ap.active_param_count * tokens / world
    return CommPlan(
        spec=ws, world=world, tokens_per_step=tokens,
        tokens_per_rank=tokens_rank,
        param_bytes=float(ap.param_count) * grad_bytes,
        grad_bytes_per_rank=float(sum(dense) + sum(experts)) * grad_bytes,
        phases=tuple(phases), flops_per_rank=flops,
        compute_seconds=flops / peak,
        stage_layers=tuple(hi - lo for lo, hi in ranges), peak_flops=peak)


# --------------------------------------------------------------------------
# rank groups and logical demand
# --------------------------------------------------------------------------

def _phase_groups(plan: CommPlan, axis: str
                  ) -> List[Tuple[int, np.ndarray]]:
    """(stage, rank ids) of every group on one group axis.  Rank layout:
    ``r = (p * dp + d) * tp + t`` (TP fastest-varying, so TP groups are
    contiguous rank blocks, and stage p is the block of ``dp * tp`` ranks
    from ``p * dp * tp``).  A ``pp`` group is one (d, t)'s chain of ranks
    over the stages; its stage is -1."""
    pp, dp, tp, ep = plan.spec.pp, plan.spec.dp, plan.spec.tp, plan.spec.ep

    def rank(p, d, t):
        return (p * dp + np.asarray(d)) * tp + t

    if axis == "pp":
        return [(-1, (np.arange(pp) * dp + d) * tp + t)
                for d in range(dp) for t in range(tp)]
    if axis == "tp":
        return [(p, rank(p, d, np.arange(tp)))
                for p in range(pp) for d in range(dp)]
    if axis == "dp":
        return [(p, rank(p, np.arange(dp), t))
                for p in range(pp) for t in range(tp)]
    if axis == "ep":
        return [(p, rank(p, b * ep + np.arange(ep), t))
                for p in range(pp) for b in range(dp // ep)
                for t in range(tp)]
    if axis == "edp":
        return [(p, rank(p, np.arange(dp // ep) * ep + e, t))
                for p in range(pp) for e in range(ep) for t in range(tp)]
    raise ValueError(f"unknown group axis {axis!r}")


def _phase_pairs(phase: CommPhase, groups: List[Tuple[int, np.ndarray]],
                 node_of: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """(u, v, bytes, rounds): one phase's logical demand a round as node
    pairs ``u -> v``, and its round count.

    Ring lowering for all-reduce (2(g-1) rounds of 1/g payload per edge),
    all-gather / reduce-scatter (g-1 rounds); full pair demand in a single
    round for all-to-all; for send/receive, one micro-batch each way between
    consecutive stages of every chain in a round.  Every group carries its
    stage's own bytes (:meth:`CommPhase.bytes_of`): stages run the phase's
    rounds together.  Demand between ranks co-located on one node is free
    (dropped here); a node pair listed twice carries both payloads.
    """
    g = phase.group_size
    us, vs, bs = [], [], []
    for stage, grp in groups:
        nodes = node_of[grp]
        if phase.collective == "all_to_all":
            us.append(np.repeat(nodes, nodes.size))
            vs.append(np.tile(nodes, nodes.size))
            bs.append(np.full(nodes.size ** 2, phase.bytes_of(stage) / g))
        elif phase.collective == "send_recv":
            us += [nodes[:-1], nodes[1:]]
            vs += [nodes[1:], nodes[:-1]]
            bs += [np.full(nodes.size - 1, phase.bytes_per_rank)] * 2
        else:
            us.append(nodes)
            vs.append(np.roll(nodes, -1))
            bs.append(np.full(nodes.size, phase.bytes_of(stage) / g))
    if phase.collective in ("all_to_all", "send_recv"):
        rounds = phase.ops_per_step
    else:
        per_op = 2 * (g - 1) if phase.collective == "all_reduce" else g - 1
        rounds = per_op * phase.ops_per_step
    u, v, b = np.concatenate(us), np.concatenate(vs), np.concatenate(bs)
    keep = u != v
    return u[keep], v[keep], b[keep], rounds


# --------------------------------------------------------------------------
# executing a plan on a topology
# --------------------------------------------------------------------------

@dataclasses.dataclass
class WorkloadResult:
    """Executed step-time breakdown of one plan on one topology.

    ``phase_rows`` carry the measured per-phase link time (seconds);
    ``step_seconds`` composes them with the compute term: TP, MoE and
    pipeline send/receive phases sit on the critical path (no pipeline
    bubble is modeled), the DP all-reduces (``dp`` and ``edp`` axes)
    overlap with :data:`DP_OVERLAP_FRACTION` of compute and only their
    exposed remainder counts.
    ``exposed_comm_fraction = (step - compute) / step``.
    """
    plan: CommPlan
    name: str                       # topology name
    n: int
    placement: str
    phase_rows: List[Dict[str, Any]]
    compute_seconds: float
    comm_seconds: float             # sum of all phase link times
    dp_seconds: float
    tp_seconds: float
    moe_seconds: float
    pp_seconds: float
    exposed_dp_seconds: float
    step_seconds: float
    exposed_comm_fraction: float
    dropped_frac: float             # demand to unreachable node pairs
    seconds: float                  # wall time (lowering + engine)

    def phase_seconds(self) -> Dict[str, float]:
        """phase name -> measured link seconds."""
        return {r["name"]: r["seconds"] for r in self.phase_rows}

    def to_dict(self) -> Dict[str, Any]:
        """JSON-ready summary."""
        return dict(
            workload=self.plan.spec.spec, topology=self.name, n=self.n,
            placement=self.placement, world=self.plan.world,
            compute_ms=round(self.compute_seconds * 1e3, 6),
            comm_ms=round(self.comm_seconds * 1e3, 6),
            dp_ms=round(self.dp_seconds * 1e3, 6),
            tp_ms=round(self.tp_seconds * 1e3, 6),
            moe_ms=round(self.moe_seconds * 1e3, 6),
            pp_ms=round(self.pp_seconds * 1e3, 6),
            exposed_dp_ms=round(self.exposed_dp_seconds * 1e3, 6),
            step_ms=round(self.step_seconds * 1e3, 6),
            exposed_comm_fraction=round(self.exposed_comm_fraction, 6),
            dropped_frac=round(self.dropped_frac, 6),
            phases=[dict(r, seconds=round(r["seconds"], 9))
                    for r in self.phase_rows],
            seconds=round(self.seconds, 3))

    def report(self) -> str:
        """Compact text block for CLI reports."""
        lines = [
            f"workload        : {self.plan.spec.spec} on {self.name} "
            f"(n={self.n}, {self.plan.world} ranks, "
            f"placement={self.placement})",
            f"step time       : {self.step_seconds * 1e3:.3f} ms "
            f"(compute {self.compute_seconds * 1e3:.3f} + comm exposed "
            f"{(self.step_seconds - self.compute_seconds) * 1e3:.3f})",
            f"exposed comm    : {self.exposed_comm_fraction:.1%} of the step",
        ]
        for r in self.phase_rows:
            lines.append(
                f"  {r['name']:<16}: {r['seconds'] * 1e3:8.3f} ms "
                f"({r['collective']}, {r['rounds']} rounds)")
        if self.dropped_frac > 0:
            lines.append(f"dropped demand  : {self.dropped_frac:.2%} "
                         "(unreachable pairs)")
        return "\n".join(lines)


@obs.traced("workloads/simulate", phase="execute")
def simulate_workload(topo: Union[Topology, Tuple[np.ndarray, int]],
                      workload: Union[str, WorkloadSpec, CommPlan], *,
                      placement: str = "linear", seed: int = 0,
                      routing: Optional[RoutingResult] = None,
                      link_bw: float = LINK_BW,
                      hop_latency: float = PER_HOP_LATENCY,
                      overlap_fraction: float = DP_OVERLAP_FRACTION,
                      chunk: int = DEFAULT_SOURCE_CHUNK) -> WorkloadResult:
    """Compile a communication plan onto a topology and execute it.

    Ranks map to nodes via :func:`repro.core.placement.place_ranks`
    (``placement`` strategy, co-located traffic free); each phase's logical
    demand is ECMP-lowered onto the padded gather-table slots and run through
    the jitted round engine of :mod:`repro.core.simulate` at the plan's real
    byte counts.

    Args:
        topo: a :class:`Topology` or ``(table, n)`` padded pair (the degraded
            entry point used by :func:`repro.core.faults.fault_sweep`).
        workload: spec string, :class:`WorkloadSpec`, or prebuilt
            :class:`CommPlan`.
        placement: rank->node strategy (``linear`` / ``round_robin`` /
            ``random``; see :func:`repro.core.placement.place_ranks`).
        seed: placement RNG seed (``random`` strategy only).
        routing: reuse an all-sources :class:`RoutingResult`.
        link_bw / hop_latency: engine constants (bytes/s, s/hop).
        overlap_fraction: fraction of compute the DP all-reduce hides behind.
        chunk: ECMP sources per jitted call (memory knob).

    Returns:
        :class:`WorkloadResult` with the per-phase and composed step times.
    """
    t0 = time.time()
    plan = workload if isinstance(workload, CommPlan) else \
        plan_workload(workload)
    name, n, table = _unpack_topo(topo)
    if routing is None:
        routing = analyze_routing((table, n), chunk=chunk)
    node_of = place_ranks(n, plan.world, strategy=placement, seed=seed)
    router = EcmpRouter(table, routing.dist, routing.sigma, chunk=chunk)
    phase_rows: List[Dict[str, Any]] = []
    axis_seconds = {"pp": 0.0, "dp": 0.0, "edp": 0.0, "tp": 0.0, "ep": 0.0}
    dropped_total = 0.0
    demand_total = 0.0
    for phase in plan.phases:
        with obs.span("workloads/demand", phase=phase.name):
            u, v, b, rounds = _phase_pairs(
                phase, _phase_groups(plan, phase.group_axis), node_of)
        with obs.span("workloads/lower", phase=phase.name, rounds=int(rounds)):
            hop = routing.dist[u, v]
            live = (hop >= 0) & (b > 0)
            hops = int(hop[live].max()) if live.any() else 0
            dropped = float(b[hop < 0].sum())
            lowered = router.pair_loads(u, v, b).astype(np.float32)
        obs.count("workloads/phases_lowered")
        sched = Schedule(
            name=name, collective=f"workload:{phase.name}", algorithm="ecmp",
            n=n, k=int(table.shape[1]), round_bytes=lowered[None],
            counts=np.array([rounds], dtype=np.int32),
            hops=np.array([hops], dtype=np.int32),
            dropped_demand=rounds * dropped)
        with obs.span("workloads/engine", phase=phase.name):
            res = run_schedule(sched, payloads=1.0, link_bw=link_bw,
                               hop_latency=hop_latency)
        secs = float(res.time_seconds[0])
        axis_seconds[phase.group_axis] += secs
        dropped_total += rounds * dropped
        demand_total += rounds * float(b.sum())
        phase_rows.append(dict(
            name=phase.name, collective=phase.collective,
            group_axis=phase.group_axis, group_size=phase.group_size,
            ops=phase.ops_per_step, rounds=int(rounds),
            bytes_per_rank=phase.bytes_per_rank, dtype=phase.dtype,
            seconds=secs,
            max_link_bytes=float(lowered.max())))
    dp_s = axis_seconds["dp"] + axis_seconds["edp"]
    tp_s, moe_s, pp_s = (axis_seconds["tp"], axis_seconds["ep"],
                         axis_seconds["pp"])
    exposed_dp = max(0.0, dp_s - overlap_fraction * plan.compute_seconds)
    step = plan.compute_seconds + tp_s + moe_s + pp_s + exposed_dp
    return WorkloadResult(
        plan=plan, name=name, n=n, placement=placement,
        phase_rows=phase_rows, compute_seconds=plan.compute_seconds,
        comm_seconds=dp_s + tp_s + moe_s + pp_s, dp_seconds=dp_s,
        tp_seconds=tp_s, moe_seconds=moe_s, pp_seconds=pp_s,
        exposed_dp_seconds=exposed_dp, step_seconds=step,
        exposed_comm_fraction=(step - plan.compute_seconds) / step
            if step > 0 else 0.0,
        dropped_frac=dropped_total / demand_total if demand_total > 0 else 0.0,
        seconds=time.time() - t0)


# --------------------------------------------------------------------------
# byte-accounting cross-check against launch/hlo_analysis
# --------------------------------------------------------------------------

def hlo_crosscheck(plan: Union[str, WorkloadSpec, CommPlan],
                   rel_tol: float = 1e-9) -> Dict[str, Any]:
    """Audit the plan's byte accounting against the independent HLO parser.

    Emits the plan as synthetic HLO (:meth:`CommPlan.to_hlo`), runs
    :func:`repro.launch.hlo_analysis.analyze_hlo` over the text, and compares
    the recovered per-kind collective bytes against
    :meth:`CommPlan.collective_byte_totals`.

    Returns a dict with ``ok`` plus per-kind
    ``{plan_bytes, hlo_bytes, ok}`` rows.
    """
    from repro.launch.hlo_analysis import analyze_hlo

    if not isinstance(plan, CommPlan):
        plan = plan_workload(plan)
    stats = analyze_hlo(plan.to_hlo())
    want = plan.collective_byte_totals()
    rows: Dict[str, Dict[str, Any]] = {}
    ok = True
    for kind in sorted(set(want) | {k for k, v in
                                    stats.collective_bytes.items() if v}):
        p = want.get(kind, 0.0)
        h = stats.collective_bytes.get(kind, 0.0)
        good = abs(p - h) <= rel_tol * max(1.0, abs(p))
        ok &= good
        rows[kind] = dict(plan_bytes=p, hlo_bytes=h, ok=good)
    return dict(ok=ok, kinds=rows)


# --------------------------------------------------------------------------
# spectral-prediction agreement
# --------------------------------------------------------------------------

def spectral_rank_correlation(rows: Sequence[Dict[str, Any]],
                              rho2_key: str = "rho2",
                              step_key: str = "step_ms") -> Optional[float]:
    """Spearman rank correlation between the spectral gap and SLOWNESS.

    Larger rho2 should mean a *smaller* step time, so the correlation between
    the rho2 ranking (descending) and the step-time ranking (ascending) is
    +1 when the spectral prediction orders the executed workload perfectly.
    Returns None with fewer than 2 rows.
    """
    pairs = [(float(r[rho2_key]), float(r[step_key])) for r in rows
             if r.get(rho2_key) is not None and r.get(step_key) is not None]
    if len(pairs) < 2:
        return None
    rho2 = np.array([p[0] for p in pairs])
    step = np.array([p[1] for p in pairs])

    def ranks(x: np.ndarray) -> np.ndarray:
        order = np.argsort(x, kind="stable")
        r = np.empty(len(x))
        r[order] = np.arange(len(x), dtype=np.float64)
        # average ties so the statistic is exact for tied values
        for v in np.unique(x):
            m = x == v
            if m.sum() > 1:
                r[m] = r[m].mean()
        return r

    a = ranks(-rho2)      # best gap first
    b = ranks(step)       # fastest step first
    a = a - a.mean()
    b = b - b.mean()
    denom = float(np.sqrt((a * a).sum() * (b * b).sum()))
    if denom == 0.0:
        return None
    return float((a * b).sum() / denom)
