"""``HybridLM`` at Ling-3.0-flash's widths, cut as the configuration file
says (published layers 1-7: a dense Kimi-Delta-Attention layer, then the
period KDA, KDA, KDA, latent attention, KDA, KDA over routed experts;
experts ``first_expert_held`` .. +8 of each layer's 512, chosen 8 a token
inside 4 of 8 groups; a 19,648-row vocabulary, the head untied), every
block recomputed in the backward, under the whole-step trainer; the loss
is the next token's cross-entropy over every position."""
import time
import jax
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.hybrid_lm import HybridLM

from benchmark.reference import ling3_flash as reference
from . import common

# build() keeps the seeded leaves here until the first batch is made:
# step 1's batch and weights are what the note below compares
_FIRST = {}


def build(cfg, specs, leaves):
    net = HybridLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=reference.kinds(cfg),
        operators={
            "kda": {"num_heads": cfg["num_attention_heads"],
                    "head_dim": cfg["head_dim"],
                    "conv_size": cfg["short_conv_kernel_size"],
                    "lower_bound": float(cfg["kda_lower_bound"]),
                    "epsilon": cfg["rms_norm_eps"],
                    "chunk": cfg["kda_chunk"]},
            "latent_attention": {
                "num_heads": cfg["num_attention_heads"],
                "kv_rank": cfg["kv_lora_rank"],
                "nope_dim": cfg["qk_nope_head_dim"],
                "rope_dim": cfg["qk_rope_head_dim"],
                "v_dim": cfg["v_head_dim"],
                "rope_theta": float(cfg["rope_theta"]),
                "rope_interleave": cfg["rope_interleave"],
                "epsilon": cfg["rms_norm_eps"], "head_gate": True}},
        dense_layers=cfg["first_k_dense_replace"],
        dense_hidden=cfg["intermediate_size"], epsilon=cfg["rms_norm_eps"],
        moe={"hidden": cfg["moe_intermediate_size"],
             "num_experts": cfg["num_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["num_experts_held"],
             "first_expert": cfg["first_expert_held"],
             "scale": cfg["routed_scaling_factor"],
             "n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
             "shared_hidden": cfg["num_shared_experts"]
             * cfg["moe_shared_expert_intermediate_size"]},
        tie_head=cfg["tie_word_embeddings"], recompute=cfg["recompute"])
    net.cast(cfg["dtype"])
    _FIRST["leaves"] = leaves
    return common.load_leaves(net, specs, leaves)


def train_step(cfg, net, optimizer):
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    vocab = cfg["vocab_size"]

    def forward(block, tokens, labels):
        return loss(block(tokens).reshape((-1, vocab)),
                    labels.reshape((-1,)))

    return common.whole_step(net, None, optimizer, forward=forward)


def batch(cfg, x, y):
    leaves = _FIRST.pop("leaves", None)
    if leaves is not None:
        # how many of step 1's (token, slot) choices fall the other way in
        # the configuration's dtype: part of the distance the limits absorb.
        # Two forwards of the reference, inside set-up (the harness calls a
        # model nowhere else): their seconds are a note too
        t0 = time.time()
        share = jax.jit(lambda p, t: reference.selection_flip_share(
            cfg, p, t))(leaves, x)
        print("note moe_selection_flip_share_%s_vs_float32 = %r"
              % (cfg["dtype"], float(share)))
        print("note selection_flip_shares_s = %r" % (time.time() - t0))
    return mx.nd.NDArray(x), mx.nd.NDArray(y)
