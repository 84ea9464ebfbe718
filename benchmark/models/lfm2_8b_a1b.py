"""``HybridLM`` at LFM2-8B-A1B's widths, cut as the configuration file says
(layers ``first_layer_held`` .. +5 of the source's 24: conv with the dense
MLP, then full_attention, conv, conv, conv with routed experts; experts
``first_expert_held`` .. +8 of each layer's 32; a 16,384-row vocabulary,
the head tied to the embedding), under the whole-step trainer; the loss is
the next token's cross-entropy over every position."""
import jax
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.hybrid_lm import HybridLM

from benchmark.reference import lfm2_8b_a1b as reference
from . import common

# build() keeps the seeded leaves here until the first batch is made:
# step 1's batch and weights are what the note below compares
_FIRST = {}


def build(cfg, specs, leaves):
    first = cfg["first_layer_held"]
    net = HybridLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=cfg["layer_types"][first:first + cfg["num_hidden_layers"]],
        operators={
            "conv": {"kernel_size": cfg["conv_L_cache"]},
            "full_attention": {
                "num_heads": cfg["num_attention_heads"],
                "num_kv_heads": cfg["num_key_value_heads"],
                "rope_theta": float(cfg["rope_theta"]),
                "epsilon": cfg["norm_eps"]}},
        dense_layers=cfg["num_dense_layers"],
        dense_hidden=cfg["intermediate_size"], epsilon=cfg["norm_eps"],
        moe={"hidden": cfg["moe_intermediate_size"],
             "num_experts": cfg["num_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["num_experts_held"],
             "first_expert": cfg["first_expert_held"],
             "scale": float(cfg["routed_scaling_factor"])},
        tie_head=cfg["tie_word_embeddings"])
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
        # the configuration's dtype: part of the distance the limits absorb
        share = jax.jit(lambda p, t: reference.selection_flip_share(
            cfg, p, t))(leaves, x)
        print("note moe_selection_flip_share_%s_vs_float32 = %r"
              % (cfg["dtype"], float(share)))
    return mx.nd.NDArray(x), mx.nd.NDArray(y)
