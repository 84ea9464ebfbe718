"""``HybridLM`` at the widths of Keye-VL-2.0-30B-A3B's language model, cut
as the configuration file says (4 of the source's 48 layers, every one
grouped attention over the keys its indexer picks, ``sa_config.topk`` a
query; experts ``first_expert_held`` .. +16 of each layer's 128, chosen 8 a
token by a softmax router; an 18,992-row vocabulary, the head untied),
under the whole-step trainer; the loss is the next token's cross-entropy
over every position."""
import time

import jax
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.hybrid_lm import HybridLM

from benchmark.reference import keye_vl2_30b_a3b as reference
from . import common

# build() keeps the seeded leaves here until the first batch is made:
# step 1's batch and weights are what the notes below compare
_FIRST = {}


def build(cfg, specs, leaves):
    sa = cfg["sa_config"]
    net = HybridLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=["sparse_attention"] * cfg["num_hidden_layers"],
        operators={"sparse_attention": {
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "epsilon": cfg["rms_norm_eps"],
            "rope_theta": float(cfg["rope_theta"]), "topk": sa["topk"],
            "index_heads": sa["indexer_num_heads"],
            "index_head_dim": sa["indexer_head_dim"]}},
        dense_layers=0, dense_hidden=0, epsilon=cfg["rms_norm_eps"],
        moe={"hidden": cfg["moe_intermediate_size"],
             "num_experts": cfg["num_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["num_experts_held"],
             "first_expert": cfg["first_expert_held"],
             "score": "softmax", "activation": cfg["hidden_act"]},
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
        # how many of step 1's selections fall the other way in the
        # configuration's dtype, the experts' (token, slot) choices and the
        # indexer's (query, key) pairs: part of the distance the limits
        # absorb. Two forwards of the reference, inside set-up (the harness
        # calls a model nowhere else): their seconds are a note too
        t0 = time.time()
        experts, keys = jax.jit(lambda p, t: reference.selection_flip_shares(
            cfg, p, t))(leaves, x)
        print("note moe_selection_flip_share_%s_vs_float32 = %r"
              % (cfg["dtype"], float(experts)))
        print("note sparse_selection_flip_share_%s_vs_float32 = %r"
              % (cfg["dtype"], float(keys)))
        print("note selection_flip_shares_s = %r" % (time.time() - t0))
    return mx.nd.NDArray(x), mx.nd.NDArray(y)
