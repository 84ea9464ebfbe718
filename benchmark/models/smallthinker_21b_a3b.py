"""``HybridLM`` at SmallThinker-21BA3B-Instruct's widths, cut as the
configuration file says (layers ``first_layer_held`` .. +4 of the source's
52: a global attention layer without position encoding, then three with
rotary and a window of 4,096; experts ``first_expert_held`` .. +8 of each
layer's 64, ReLU-gated, chosen by a softmax router that reads the layer's
input; an 18,992-row vocabulary, the head untied), under the whole-step
trainer; the loss is the next token's cross-entropy over every position."""
import jax
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.hybrid_lm import HybridLM

from benchmark.reference import smallthinker_21b_a3b as reference
from . import common

# build() keeps the seeded leaves here until the first batch is made:
# step 1's batch and weights are what the note below compares
_FIRST = {}


def build(cfg, specs, leaves):
    first, n = cfg["first_layer_held"], cfg["num_hidden_layers"]
    windowed = cfg["sliding_window_layout"][first:first + n]
    if windowed != cfg["rope_layout"][first:first + n]:
        raise ValueError("rope_layout differs from sliding_window_layout: "
                         "the source turns exactly its windowed layers")
    heads = {"num_heads": cfg["num_attention_heads"],
             "num_kv_heads": cfg["num_key_value_heads"],
             "head_dim": cfg["head_dim"], "epsilon": cfg["rms_norm_eps"],
             "qk_norm": False}
    net = HybridLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=["window_attention" if w else "full_attention"
                for w in windowed],
        operators={
            "full_attention": dict(heads, rope=False),
            "window_attention": dict(
                heads, window=cfg["sliding_window_size"],
                rope_theta=float(cfg["rope_theta"]))},
        dense_layers=0, dense_hidden=0, epsilon=cfg["rms_norm_eps"],
        moe={"hidden": cfg["moe_ffn_hidden_size"],
             "num_experts": cfg["moe_num_primary_experts"],
             "top_k": cfg["moe_num_active_primary_experts"],
             "experts_held": cfg["moe_num_primary_experts_held"],
             "first_expert": cfg["first_expert_held"],
             "score": "softmax" if cfg["moe_primary_router_apply_softmax"]
             else "sigmoid",
             "activation": "relu"},
        router_ahead=True, tie_head=cfg["tie_word_embeddings"])
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
