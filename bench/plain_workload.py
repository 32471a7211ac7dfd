"""Plain reference of one DeepSeek-V3 training step on a fabric: numpy,
float64, no program code.

Written from the published numbers and the documented lowering alone:

* the parameter table of DeepSeek-V3 (config.json: hidden 7168, 61 layers,
  the first 3 dense with FFN 18432, 256 routed experts of width 2048 and 8 a
  token, 1 shared expert, MLA with q_lora_rank 1536, kv_lora_rank 512, 128
  heads of 128 + 64 query/key and 128 value dims, vocabulary 129,280 untied,
  one MTP layer), its training shape (4,096-token sequences) and numerics
  (dispatch in FP8, everything else exchanged in BF16, no token dropped);
* the layout: rank ``r = p * dp + d`` (no tensor parallelism), stage p holds
  the contiguous layers ``stages`` gives it, the embedding on stage 0, the
  head, final norm and MTP layer on the last; rank r on node ``r * n //
  world`` (``linear`` placement);
* each phase's demand: ring all-reduces of each stage's bucketized
  gradient, non-expert over the stage's dp ranks and expert over the dp/ep
  replicas of each expert shard; uniform all-to-all within each EP block of
  ep consecutive d; one sequence's activations each way between
  consecutive stages a round.  Stages run a phase's rounds together, each
  stage's total spread over the largest stage's op count;
* the fabric: BFS hops and minimal-path counts from :mod:`plain`, minimal
  path (ECMP) loads of each phase's demand from them, and the engine time
  ``rounds * (max link bytes / LINK_BW + hops * HOP_LATENCY)`` of each phase
  (one demand a round, repeated), ``hops`` the longest hop count of any
  demand pair;
* the step: compute (6 x activated parameters x tokens / ranks at the
  chip's peak) + all-to-all + send/receive + the all-reduces less the part
  hidden behind two thirds of compute.

``rnd`` rounds every link load the ECMP pass computes (the bfloat16
control); the default keeps float64 throughout.
"""
from __future__ import annotations

import math
import os
from typing import List, Optional, Tuple

import numpy as np

import plain

# -- the published configuration -----------------------------------------
D_MODEL = 7168
LAYERS = 61
DENSE_LAYERS = 3
FFN = 18432
EXPERTS = 256
EXPERTS_PER_TOKEN = 8
EXPERT_FFN = 2048
SHARED_EXPERTS = 1
Q_LORA = 1536
KV_LORA = 512
HEADS = 128
QK_NOPE = 128
QK_ROPE = 64
V_HEAD = 128
VOCAB = 129280
MTP_LAYERS = 1
SEQ_LEN = 4096

# -- numerics and the simulator's constants ------------------------------
DISPATCH_BYTES = 1          # FP8 dispatch
BF16 = 2                    # combine, backward legs, activations, gradients
BUCKET = 2.0 ** 27          # gradient all-reduce bucket, bytes
LINK_BW = 50e9              # bytes/s a directed link
HOP_LATENCY = 1e-6          # seconds a hop
OVERLAP = 2.0 / 3.0         # share of compute the all-reduces hide behind


def layer(i: Optional[int]) -> Tuple[int, int, bool]:
    """(non-expert parameters, routed-expert parameters, routes tokens) of
    main layer i, or of the MTP layer for ``None``."""
    attn = (D_MODEL * Q_LORA + Q_LORA * HEADS * (QK_NOPE + QK_ROPE)
            + D_MODEL * (KV_LORA + QK_ROPE)
            + KV_LORA * HEADS * (QK_NOPE + V_HEAD) + HEADS * V_HEAD * D_MODEL)
    norms = 2 * D_MODEL + Q_LORA + KV_LORA
    if i is not None and i < DENSE_LAYERS:
        return attn + norms + 3 * D_MODEL * FFN, 0, False
    other = attn + norms + SHARED_EXPERTS * 3 * D_MODEL * EXPERT_FFN \
        + D_MODEL * EXPERTS
    if i is None:           # MTP: the 2D -> D projection and three norms
        other += 2 * D_MODEL * D_MODEL + 3 * D_MODEL
    return other, EXPERTS * 3 * D_MODEL * EXPERT_FFN, True


def activated() -> float:
    """Parameters a token passes through, MTP layer, embedding and head
    included."""
    total = 2 * VOCAB * D_MODEL + D_MODEL
    for i in list(range(LAYERS)) + [None] * MTP_LAYERS:
        other, experts, _ = layer(i)
        total += other + experts * EXPERTS_PER_TOKEN / EXPERTS
    return total


def stages(pp: int) -> List[dict]:
    """Per stage: main layers, non-expert and expert parameters, MoE
    layers (the first ``LAYERS % pp`` stages take one layer more)."""
    out, lo = [], 0
    for p in range(pp):
        hi = lo + LAYERS // pp + (1 if p < LAYERS % pp else 0)
        members = list(range(lo, hi)) + ([None] * MTP_LAYERS
                                         if p == pp - 1 else [])
        st = dict(layers=hi - lo, other=0, experts=0, moe=0)
        for i in members:
            other, experts, moe = layer(i)
            st["other"] += other
            st["experts"] += experts
            st["moe"] += moe
        if p == 0:
            st["other"] += VOCAB * D_MODEL
        if p == pp - 1:
            st["other"] += VOCAB * D_MODEL + D_MODEL
        out.append(st)
        lo = hi
    return out


def phases(pp: int, dp: int, ep: int, batch: int, n: int) -> List[dict]:
    """Each phase's name, rounds and demand a round as node triples
    ``(u, v, bytes)`` (pairs on one node dropped; repeated pairs add)."""
    world = pp * dp
    node = np.arange(world) * n // world
    st = stages(pp)
    out = []

    def finish(name, u, v, b, rounds):
        u, v = node[np.concatenate(u)], node[np.concatenate(v)]
        b = np.concatenate(b)
        keep = u != v
        out.append(dict(name=name, u=u[keep], v=v[keep], b=b[keep],
                        rounds=rounds))

    def ring(name, totals, groups_of):
        ops = max(max(1, math.ceil(t / BUCKET)) for t in totals)
        u, v, b = [], [], []
        for p, t in enumerate(totals):
            for ranks in groups_of(p):
                g = len(ranks)
                u.append(ranks)
                v.append(np.roll(ranks, -1))
                b.append(np.full(g, t / ops / g))
        finish(name, u, v, b, 2 * (g - 1) * ops)

    split = ep > 1
    other = [(s["other"] + (0 if split else s["experts"])) * BF16 for s in st]
    ring("dp_allreduce", other, lambda p: [p * dp + np.arange(dp)])
    if split and dp // ep > 1:
        ring("dp_allreduce_experts", [s["experts"] / ep * BF16 for s in st],
             lambda p: [p * dp + e + ep * np.arange(dp // ep)
                        for e in range(ep)])
    if split:
        tokens = batch // dp * SEQ_LEN
        most = max(s["moe"] for s in st)
        for name, width, legs in (("moe_dispatch", DISPATCH_BYTES, 1),
                                  ("moe_combine", BF16, 3)):
            u, v, b = [], [], []
            for p, s in enumerate(st):
                per_op = tokens * EXPERTS_PER_TOKEN * D_MODEL * width \
                    * s["moe"] / most
                for blk in range(dp // ep):
                    ranks = p * dp + blk * ep + np.arange(ep)
                    u.append(np.repeat(ranks, ep))
                    v.append(np.tile(ranks, ep))
                    b.append(np.full(ep * ep, per_op / ep))
            finish(name, u, v, b, legs * most)
    if pp > 1:
        fwd = np.arange((pp - 1) * dp)
        act = np.full(fwd.size, float(SEQ_LEN * D_MODEL * BF16))
        finish("pp_sendrecv", [fwd, fwd + dp], [fwd + dp, fwd], [act, act],
               batch // dp)
    return out


def _block(job: tuple) -> tuple:
    """Per phase, the ECMP loads (n, width) of the demand of one block of
    sources, the longest hop count of a served pair, and the demand dropped
    to unreachable targets.  ``job`` is ``(n, edges, sources, phases, rnd)``.

    BFS distances and minimal-path counts come from :func:`plain.
    bfs_paths_loads`; the loads are the Brandes-style backward pass of each
    source over its BFS layers, all phases at once: the demand subtree g
    routed through v is its own demand plus sigma(v) times the sum of g /
    sigma over v's successors, and the slot u -> v one layer further out
    carries sigma(u) * g(v) / sigma(v)."""
    n, edges, sources, ph, rnd = job
    r = rnd or (lambda x: x)
    table, _ = plain.neighbor_table(n, edges)
    dist, sigma, _, _ = plain.bfs_paths_loads(table, sources,
                                              lambda s: np.zeros(n))
    width = table.shape[1]
    pad = table < 0
    nbr = np.where(pad, np.arange(n)[:, None], table)
    M = len(ph)
    rows = np.zeros((M, sources.size, n))
    at = np.full(n, -1)
    at[sources] = np.arange(sources.size)
    for m, p in enumerate(ph):
        mine = at[p["u"]] >= 0
        np.add.at(rows[m], (at[p["u"][mine]], p["v"][mine]), p["b"][mine])
    reach = dist >= 0
    served = np.where(reach[None], rows, 0.0)
    dropped = (rows - served).reshape(M, -1).sum(axis=1)
    hops = [int(dist[sv > 0].max()) if (sv > 0).any() else 0
            for sv in served]
    loads = np.zeros((M, n, width))
    for i in range(sources.size):
        d, sg = dist[i], sigma[i]
        w = np.where((d > 0)[:, None], served[:, i, :].T, 0.0)      # (n, M)
        if not w.any():
            continue
        g = r(w.copy())
        safe = np.where(sg > 0, sg, 1.0)
        order = np.argsort(d, kind="stable")
        bounds = np.searchsorted(d[order], np.arange(d.max() + 2))
        for depth in range(int(d.max()) - 1, 0, -1):
            lay = order[bounds[depth]:bounds[depth + 1]]
            nb = nbr[lay]
            succ = (d[nb] == depth + 1) & ~pad[lay]
            inc = np.where(succ[:, :, None],
                           g[nb] / safe[nb][:, :, None], 0.0).sum(axis=1)
            g[lay] = r(w[lay] + sg[lay][:, None] * inc)
        ratio = np.where((d > 0)[:, None], g / safe[:, None], 0.0)
        succ = (d[nbr] == d[:, None] + 1) & ~pad & (d[:, None] >= 0)
        loads += r(np.where(succ[None], sg[None, :, None]
                            * np.moveaxis(ratio[nbr], 2, 0), 0.0))
    return loads, hops, dropped


def steps(n: int, graphs: List[np.ndarray], pp: int, dp: int, ep: int,
          batch: int, peak_flops: float, rnd=None) -> List[dict]:
    """Phase seconds, step seconds and dropped share of one training step on
    each graph ``(n, edges)`` of ``graphs``.  From n = 1,024 the sources are
    split into blocks run by one process a core (up to 16), else all run in
    this process; loads add over sources, so the blocks' sums are the
    loads."""
    ph = phases(pp, dp, ep, batch, n)
    workers = min(16, max(1, len(os.sched_getaffinity(0)) - 1)) \
        if n >= 1024 else 1
    blocks = np.array_split(np.arange(n), workers)
    jobs = [(n, np.asarray(e), blk, ph, rnd) for e in graphs for blk in blocks]
    if workers > 1:
        import multiprocessing

        with multiprocessing.get_context("spawn").Pool(workers) as pool:
            parts = pool.map(_block, jobs)
    else:
        parts = [_block(job) for job in jobs]
    compute = 6.0 * activated() * batch * SEQ_LEN / (pp * dp) / peak_flops
    out = []
    for k in range(len(graphs)):
        mine = parts[k * len(blocks):(k + 1) * len(blocks)]
        loads = sum(p[0] for p in mine)
        secs, dropped, total = {}, 0.0, 0.0
        for m, p in enumerate(ph):
            hops = max(q[1][m] for q in mine)
            secs[p["name"]] = p["rounds"] * (
                loads[m].max() / LINK_BW + hops * HOP_LATENCY)
            dropped += p["rounds"] * sum(q[2][m] for q in mine)
            total += p["rounds"] * p["b"].sum()
        ar = sum(v for key, v in secs.items() if key.startswith("dp_allreduce"))
        rest = sum(secs.values()) - ar
        out.append(dict(phase_seconds=secs,
                        step=compute + rest + max(0.0, ar - OVERLAP * compute),
                        dropped_frac=dropped / total if total > 0 else 0.0))
    return out
