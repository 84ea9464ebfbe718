"""``HybridLM`` at Qwen3-Next-80B-A3B-Instruct's widths, cut as the
configuration file says (published layers ``first_layer_held`` .. +3, one
whole period: Gated DeltaNet, Gated DeltaNet, Gated DeltaNet, gated
attention, every one over routed experts; 32 value heads over 16 key heads
of 128 in a DeltaNet layer, 16 query heads over 2 key/value heads of 256
with an elementwise output gate and rotary over a head's first 64 entries
in the attention layer; every norm but the DeltaNet's head norm scales by
``1 + w``; experts ``first_expert_held`` .. +32 of each layer's 512, 10 a
token by a softmax over all, and a shared expert behind a sigmoid gate; an
18,992-row vocabulary, the head untied), under the whole-step trainer; the
loss is the next token's cross-entropy over every position."""
import time

import jax
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.hybrid_lm import HybridLM

from benchmark.reference import qwen3_next_80b_a3b as reference
from . import common

# build() keeps the seeded leaves here until the first batch is made:
# step 1's batch and weights are what the notes below count
_FIRST = {}
_NOTE_TOKENS = 2048


def build(cfg, specs, leaves):
    turned = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    if cfg["rope_scaling"] is not None or cfg["use_sliding_window"] \
            or cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("a scaled table, a window, a dense layer or a "
                         "sparse step other than 1 is not built")
    net = HybridLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=reference.kinds(cfg),
        operators={
            "gated_delta_net": {
                "num_heads": cfg["linear_num_value_heads"],
                "num_key_heads": cfg["linear_num_key_heads"],
                "head_dim": cfg["linear_key_head_dim"],
                "value_head_dim": cfg["linear_value_head_dim"],
                "conv_size": cfg["linear_conv_kernel_dim"],
                "epsilon": cfg["rms_norm_eps"], "chunk": cfg["gdn_chunk"]},
            "full_attention": {
                "num_heads": cfg["num_attention_heads"],
                "num_kv_heads": cfg["num_key_value_heads"],
                "head_dim": cfg["head_dim"], "epsilon": cfg["rms_norm_eps"],
                "qk_norm": True, "zero_centered": True, "element_gate": True,
                "rope_theta": float(cfg["rope_theta"]),
                "rotary_dim": 0 if turned == cfg["head_dim"] else turned}},
        dense_layers=0, dense_hidden=cfg["intermediate_size"],
        epsilon=cfg["rms_norm_eps"], zero_centered=True,
        moe={"hidden": cfg["moe_intermediate_size"],
             "num_experts": cfg["num_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["num_experts_held"],
             "first_expert": cfg["first_expert_held"],
             "score": "softmax",
             "shared_hidden": cfg["shared_expert_intermediate_size"],
             "shared_gate": True},
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

    step = common.whole_step(net, None, optimizer, forward=forward)
    # as laguna's builder: the whole-step trainer computes its gradients
    # inside its program, and the zero buffer eager autograd attached to
    # each leaf when it was loaded (2 bytes a parameter, 1.2 GiB here) is
    # never written: released the public way
    for leaf in net.collect_params().values():
        leaf.grad_req = "null"
    return step


def batch(cfg, x, y):
    leaves = _FIRST.pop("leaves", None)
    if leaves is not None:
        # of step 1's choices over the sequence's first ``_NOTE_TOKENS``
        # positions (a causal model: a prefix's choices are the sequence's;
        # the reference walks the recurrence token by token, and the whole
        # 16,384 twice would be a minute of every run's set-up): the rows
        # this chip's experts draw in each layer (an even share is what the
        # sizing rests on), and how many fall the other way in the
        # configuration's dtype (part of the distance the limits absorb).
        # Two forwards of the reference, inside set-up (the harness calls a
        # model nowhere else): their seconds are a note too
        t0 = time.time()
        head = x[:, :_NOTE_TOKENS]
        counts = jax.jit(lambda p, t: reference.selection_counts(cfg, p, t))
        rows, flipped = counts(leaves, head)
        print("note moe_rows_held_by_layer = %r of %d positions (an even "
              "share: %r)" % (
                  [int(r) for r in rows], head.shape[1],
                  head.size * cfg["num_experts_per_tok"]
                  * cfg["num_experts_held"] / cfg["num_experts"]))
        print("note moe_selection_flip_share_%s_vs_float32 = %r"
              % (cfg["dtype"], float(flipped)))
        # unload it: a loaded program keeps its scratch, and the step's
        # program needs the room
        counts.clear_cache()
        print("note selection_counts_s = %r" % (time.time() - t0))
    return mx.nd.NDArray(x), mx.nd.NDArray(y)
