"""DeepSeek-V3 [arXiv:2412.19437] as a shape-only parameter table.

The LM stack of :mod:`repro.models` has no multi-head latent attention
(MLA), shared experts, leading dense layers or multi-token prediction (MTP),
so this architecture is not an :class:`~repro.configs.base.ArchConfig`.  It
is the published ``config.json``
(https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json)
written out as parameter counts, which is all the workload planner
(:mod:`repro.core.workloads`) reads: per layer, the elements of each
parameter group, and the embedding, head and MTP layer beside them.

Counts, in parameters (multiply by a dtype width for bytes):

* 61 layers, 671.03 B parameters with embedding and head (the report's
  671 B); 37.55 B activated a token (8 of 256 routed experts, the shared
  expert, embedding and head; the report's 37 B);
* the MTP module (one layer) adds 11.61 B; it shares the embedding and the
  head with the main model, so they are not counted twice.

The router's ``e_score_correction_bias`` (256 a MoE layer) is a balancing
term moved outside the gradient step, so no gradient exchanges it and it is
left out.
"""
from __future__ import annotations

from typing import Dict

from .base import WORKLOAD_SHAPES

__all__ = ["NAME", "CONFIG", "SHAPE", "GROUPS", "layer_param_groups",
           "mtp_param_groups", "embedding_params", "head_params",
           "param_count", "active_param_count"]

NAME = "deepseek-v3"

#: the values of the published config.json the counts need
CONFIG = dict(
    hidden_size=7168,
    num_hidden_layers=61,
    first_k_dense_replace=3,
    intermediate_size=18432,
    n_routed_experts=256,
    num_experts_per_tok=8,
    n_shared_experts=1,
    moe_intermediate_size=2048,
    q_lora_rank=1536,
    kv_lora_rank=512,
    num_attention_heads=128,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    vocab_size=129280,
    tie_word_embeddings=False,
    num_nextn_predict_layers=1,
)

#: the report's pre-training shape (§4.2): 4,096-token sequences at a global
#: batch of 15,360 sequences
SHAPE = WORKLOAD_SHAPES["train_4k_gb15360"]

#: parameter groups of one layer, in the order :func:`layer_param_groups`
#: gives them
GROUPS = ("attention", "dense_ffn", "routed_experts", "shared_expert",
          "router", "norms")


def _attention() -> int:
    """MLA: the query's low-rank pair, the joint key-value compression with
    the decoupled RoPE key, its up-projection, and the output projection."""
    c = CONFIG
    D, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    q = D * c["q_lora_rank"] + c["q_lora_rank"] * H * qk
    kv = D * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"] + c["v_head_dim"])
    o = H * c["v_head_dim"] * D
    return q + kv + o


def _ffn(width: int) -> int:
    """A SwiGLU feed-forward block: gate, up and down projections."""
    return 3 * CONFIG["hidden_size"] * width


def layer_param_groups(layer: int) -> Dict[str, int]:
    """Parameter counts of main-model layer ``layer`` (0-based), by group.

    The first ``first_k_dense_replace`` layers have a dense FFN; the others
    route through 256 experts of width 2,048 beside one shared expert.
    Norms are the two RMSNorms of the block and the latent query and
    key-value norms of MLA.
    """
    c = CONFIG
    if not 0 <= layer < c["num_hidden_layers"]:
        raise ValueError(f"DeepSeek-V3 has layers 0..{c['num_hidden_layers'] - 1}"
                         f", not {layer}")
    moe = layer >= c["first_k_dense_replace"]
    D, E = c["hidden_size"], c["n_routed_experts"]
    return dict(
        attention=_attention(),
        dense_ffn=0 if moe else _ffn(c["intermediate_size"]),
        routed_experts=E * _ffn(c["moe_intermediate_size"]) if moe else 0,
        shared_expert=c["n_shared_experts"] * _ffn(c["moe_intermediate_size"])
        if moe else 0,
        router=D * E if moe else 0,
        norms=2 * D + c["q_lora_rank"] + c["kv_lora_rank"])


def mtp_param_groups() -> Dict[str, int]:
    """The MTP layer: a MoE block as the main model's, the projection of the
    concatenated hidden state and next-token embedding (2D -> D), and its
    two input norms and output norm; embedding and head are shared."""
    D = CONFIG["hidden_size"]
    groups = layer_param_groups(CONFIG["num_hidden_layers"] - 1)
    groups["projection"] = 2 * D * D
    groups["norms"] += 3 * D
    return groups


def embedding_params() -> int:
    return CONFIG["vocab_size"] * CONFIG["hidden_size"]


def head_params() -> int:
    """The output head and the final norm."""
    return CONFIG["vocab_size"] * CONFIG["hidden_size"] + CONFIG["hidden_size"]


def param_count(mtp: bool = False) -> int:
    """Parameters of the 61-layer model with embedding and head, and of the
    MTP layer when ``mtp``."""
    total = embedding_params() + head_params() + sum(
        sum(layer_param_groups(i).values())
        for i in range(CONFIG["num_hidden_layers"]))
    return total + (sum(mtp_param_groups().values()) if mtp else 0)


def _active(groups: Dict[str, int]) -> int:
    c = CONFIG
    share = c["num_experts_per_tok"] / c["n_routed_experts"]
    return sum(groups.values()) - groups["routed_experts"] + int(
        groups["routed_experts"] * share)


def active_param_count(mtp: bool = False) -> int:
    """Parameters a token passes through: 8 of the 256 routed experts of each
    MoE layer, everything else, the embedding and head counted."""
    total = embedding_params() + head_params() + sum(
        _active(layer_param_groups(i))
        for i in range(CONFIG["num_hidden_layers"]))
    return total + (_active(mtp_param_groups()) if mtp else 0)


#: the training run's numerics (report §3.3): MoE dispatch in FP8, combine
#: and backward legs in BF16; gradients are exchanged in BF16 (assumed); no
#: token is dropped, so no capacity factor pads the all-to-all
DISPATCH_DTYPE = "float8_e4m3fn"
COMPUTE_DTYPE = "bfloat16"
GRAD_DTYPE = "bfloat16"
