"""``LatentMoELM`` at kanana-2-30b-a3b's widths, cut as the configuration
file says (6 layers, experts ``first_expert_held`` .. +16 of each layer's
128, a 16,032-row vocabulary), under the whole-step trainer; the loss is
the next token's cross-entropy over every position."""
import jax
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.latent_moe import LatentMoELM

from benchmark.reference import kanana2_30b_a3b as reference
from . import common

# build() keeps the seeded leaves here until the first batch is made:
# step 1's batch and weights are what the note below compares
_FIRST = {}


def build(cfg, specs, leaves):
    expert = cfg["moe_intermediate_size"]
    net = LatentMoELM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        dense_hidden=cfg["intermediate_size"], epsilon=cfg["rms_norm_eps"],
        attention={"num_heads": cfg["num_attention_heads"],
                   "kv_rank": cfg["kv_lora_rank"],
                   "nope_dim": cfg["qk_nope_head_dim"],
                   "rope_dim": cfg["qk_rope_head_dim"],
                   "v_dim": cfg["v_head_dim"],
                   "rope_theta": float(cfg["rope_theta"]),
                   "rope_interleave": cfg["rope_interleave"]},
        moe={"hidden": expert, "num_experts": cfg["n_routed_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["n_routed_experts_held"],
             "first_expert": cfg["first_expert_held"],
             "scale": cfg["routed_scaling_factor"],
             "shared_hidden": cfg["n_shared_experts"] * expert})
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
