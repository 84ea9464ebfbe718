"""``HybridLM`` at Laguna-S-2.1's widths, cut as the configuration file
says (published layers ``first_layer_held`` .. +5: the leading dense layer
under full attention, then the period windowed, windowed, windowed, full
over routed experts; 48 query heads on a full layer and 72 on a windowed
one over 8 key/value heads of 128, a head-wise output gate on all; a full
layer turns half of each head by YaRN's table, a windowed one the whole
head by the plain one; experts ``first_expert_held`` .. +8 of each layer's
256, 10 a token by sigmoid scores, and a shared expert; a 12,544-row
vocabulary, the head untied), under the whole-step trainer; the loss is
the next token's cross-entropy over every position."""
import time

import jax
import mxtpu as mx
from mxtpu import gluon
from mxtpu.gluon.model_zoo.hybrid_lm import HybridLM

from benchmark.reference import laguna_s_2_1 as reference
from . import common

# build() keeps the seeded leaves here until the first batch is made:
# step 1's batch and weights are what the notes below count
_FIRST = {}

_KINDS = {"full_attention": "full_attention",
          "sliding_attention": "window_attention"}


def _operator(cfg, kind, heads):
    """The keyword arguments of one kind of the source's attention layers:
    its own head count, its own ``rope_parameters`` group."""
    rope = cfg["rope_parameters"][kind]
    if rope["rope_type"] not in ("default", "yarn"):
        raise ValueError("rope_type %r is not built" % rope["rope_type"])
    turned = int(cfg["head_dim"] * rope["partial_rotary_factor"])
    return {"num_heads": heads, "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "epsilon": cfg["rms_norm_eps"],
            "qk_norm": True, "head_gate": cfg["gating"] == "per-head",
            "rope_theta": float(rope["rope_theta"]),
            "rotary_dim": 0 if turned == cfg["head_dim"] else turned,
            "rope_scaling": rope if rope["rope_type"] == "yarn" else None,
            "window": cfg["sliding_window"]
            if kind == "sliding_attention" else 0}


def build(cfg, specs, leaves):
    first, n = cfg["first_layer_held"], cfg["num_hidden_layers"]
    kept = range(first, first + n)
    kinds = [cfg["layer_types"][l] for l in kept]
    heads = {}
    for l, kind in zip(kept, kinds):
        if heads.setdefault(kind, cfg["num_attention_heads_per_layer"][l]) \
                != cfg["num_attention_heads_per_layer"][l]:
            raise ValueError("layers of kind %s differ in their heads" % kind)
    dense = [l for l in kept if l in cfg["mlp_only_layers"]]
    if dense != list(kept)[:len(dense)]:
        raise ValueError("the dense layers kept are not the leading ones")
    net = HybridLM(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        layers=[_KINDS[kind] for kind in kinds],
        operators={_KINDS[kind]: _operator(cfg, kind, h)
                   for kind, h in heads.items()},
        dense_layers=len(dense), dense_hidden=cfg["intermediate_size"],
        epsilon=cfg["rms_norm_eps"],
        moe={"hidden": cfg["moe_intermediate_size"],
             "num_experts": cfg["num_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["num_experts_held"],
             "first_expert": cfg["first_expert_held"],
             "scale": cfg["moe_routed_scaling_factor"],
             "shared_hidden": cfg["shared_expert_intermediate_size"]},
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
    # The whole-step trainer computes its gradients inside its program and
    # has noted by now which leaves it trains. The buffer eager autograd
    # attached to each of them when it was loaded (zeros, 2 bytes a
    # parameter, 1.5 GiB here) is never written, and the step's program
    # does not load beside it (6.05 GiB held without it + 8.77 of the
    # program's own, of 15.75: PERF.md section 6, PR 45): released the
    # public way, the setter that frees a leaf's gradient
    for leaf in net.collect_params().values():
        leaf.grad_req = "null"
    return step


def batch(cfg, x, y):
    leaves = _FIRST.pop("leaves", None)
    if leaves is not None:
        # of step 1's choices: the rows this chip's experts draw in each
        # expert layer (an even share is what the sizing rests on), and how
        # many fall the other way in the configuration's dtype (part of the
        # distance the limits absorb). Two forwards of the reference,
        # inside set-up (the harness calls a model nowhere else): their
        # seconds are a note too
        t0 = time.time()
        counts = jax.jit(lambda p, t: reference.selection_counts(cfg, p, t))
        rows, flipped = counts(leaves, x)
        print("note moe_rows_held_by_layer = %r (an even share: %r)" % (
            [int(r) for r in rows],
            x.size * cfg["num_experts_per_tok"] * cfg["num_experts_held"]
            / cfg["num_experts"]))
        print("note moe_selection_flip_share_%s_vs_float32 = %r"
              % (cfg["dtype"], float(flipped)))
        # unload it: a loaded program keeps its scratch (3.8 GiB here),
        # and the step's program needs the room
        counts.clear_cache()
        print("note selection_counts_s = %r" % (time.time() - t0))
    return mx.nd.NDArray(x), mx.nd.NDArray(y)
