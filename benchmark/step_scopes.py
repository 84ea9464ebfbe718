"""The step's device time by the program's own names: what the readers of
``fwd_ms.train`` ... ``batchnorm_ms.train`` share. The harness hands a reader
the traced stretch's whole operation table (``ctx["trace"]["ops"]``: seconds
by instruction name, mean over the chips' planes); the program keeps the
operation table of the executable it runs (``mxtpu.xprof.step_operations``:
every instruction's ``op_name``, the ``jax.named_scope`` path it was traced
under, and the ``conditional`` / ``while`` it runs inside). :func:`matrix`
joins the two ONCE a run and every reader takes a row or a column of it.

How an operation is counted:

* under its own ``op_name``; one without (a copy the compiler put in) that
  runs inside a switch or a loop under that one's, and otherwise under no
  name: ``unattributed_step_pct.train``, with the operations of the trace
  that the table does not hold;
* a ``conditional`` / ``while`` by its SELF time, its seconds less those of
  the operations inside it, so a routed layer's switches and a selection's
  loops are read through to what runs in them and nothing is counted twice;
* milliseconds a step: seconds over the step module's runs a chip, as
  ``kernel_share.seconds_a_call`` counts runs.

By transform (``xprof.transform_of``): ``optimizer`` (the step's own scope),
``recomputed`` (``rematted_computation``), ``backward`` (``transpose(``),
else ``forward``. By layer kind (:func:`kind_of`, over the three passes): a
layer is the block that follows the stack's name ``h_`` on the path
(``.../h_/decoderblock3_/attn_/q_/dot_general``), and inside a layer the
first block's name decides: ``norm*`` / ``layernorm*`` (and what lies under
no block: the residual sums) ``layer_glue``; ``mlp*`` ``ffn_dense``; the
routed layer ``moe_`` by its scopes (``moe.route`` -> ``moe_route``,
``moe.shared`` / ``moe.shared_gate`` -> ``ffn_dense``, the rest
``moe_experts``); any other block is the layer's token mixer, whole, whatever
its kind (attention, KDA, Gated DeltaNet, the short convolution: a new
operator is a mixer the day it is added). Outside the layers: ``head_loss``
(embeddings, the final norm, the head, the loss). A ResNet's operations by
their innermost block: ``conv*``, ``batchnorm*``, else ``other``.

A program without the table (the parent of the PR that brought it), a run
without a trace, and a table that matches nothing read None everywhere. The
whole matrix (kind x transform, by layer, by scope, and the forty longest
operations with the names the program gave them) goes to
``chiprun_out/benchmark/<cell>/step_by_scope.json``.
"""
import heapq
import json
import os
import re

from mxtpu import xprof

TRANSFORMS = ("forward", "recomputed", "backward", "optimizer")
KINDS = ("mixer", "ffn_dense", "moe_route", "moe_experts", "head_loss",
         "layer_glue")
STACK = "h_"
TOP = 40            # the longest operations kept by name in the matrix
# JAX's own words on a path, left out of a scope's label
_OWN = re.compile(r"^(jit|pjit|jvp|transpose|checkpoint|rematted_computation|"
                  r"cond|while|closed_call|custom_vjp_call|"
                  r"custom_jvp_call|custom_vjp_call_jaxpr|branch_\d+_fun|"
                  r"forward)$")


def is_block(word):
    """A block's own name on a path: its prefix (they end in ``_``) or its
    key among its parent's children (a number)."""
    return word.endswith("_") or word.isdigit()


def scopes_of(words):
    """An ``op_name``'s words (``xprof.scope_path``) less JAX's own, the
    names of jitted helpers and the primitive at the end: the blocks and
    the operators' scopes, outermost first."""
    kept, skip = [], False
    for w in words[:-1]:
        if skip or _OWN.match(w):
            # and the function a jit names, a loop's ``body`` or ``cond``
            skip = w in ("jit", "pjit", "while")
            continue
        kept.append(w)
    return kept


def kind_of(scopes):
    """:func:`scopes_of` a path -> (the layer kind, the layer's index or
    None outside the layers)."""
    at = max((i for i, w in enumerate(scopes) if w == STACK), default=None)
    if at is None or at + 1 >= len(scopes) or not is_block(scopes[at + 1]):
        return "head_loss", None
    index = re.search(r"(\d+)_?$", scopes[at + 1])
    index = int(index.group(1)) if index else None
    rest = scopes[at + 2:]
    first = next((w for w in rest if is_block(w)), "")
    if first.startswith("moe"):
        if "moe.route" in rest:
            return "moe_route", index
        if "moe.shared" in rest or "moe.shared_gate" in rest:
            return "ffn_dense", index
        return "moe_experts", index
    if first.startswith("mlp"):
        return "ffn_dense", index
    if not first or first.startswith(("norm", "layernorm")):
        return "layer_glue", index
    return "mixer", index


def conv_kind_of(scopes):
    """A convolutional network's operation by its innermost block."""
    inner = next((w for w in reversed(scopes) if is_block(w)
                  and not w.isdigit()), "")
    for kind in ("conv", "batchnorm"):
        if inner.startswith(kind):
            return kind
    return "other"


def label_of(scopes):
    """The path for a table: from the stack of layers on (the layer's
    number starred), or from the top outside it."""
    kept = list(scopes)
    if STACK in kept:
        kept = kept[len(kept) - 1 - kept[::-1].index(STACK):]
        if len(kept) > 1:
            kept[1] = re.sub(r"\d+", "*", kept[1])
    # a backward names the model twice: transpose(jvp(net_))/jvp(net_)
    return "/".join(dict.fromkeys(kept)) or "(step)"


def join(trace, table):
    """``trace`` as ``trace_reduce.reduce`` gives it and the program's
    ``table`` -> the matrix (a dict of plain numbers, ms a step), or None
    where the trace has no module's run or no operation is in the table."""
    if not trace or not trace.get("modules") or not trace.get("ops"):
        return None
    runs = len(max(trace["modules"].values(), key=sum)) / trace["planes"]
    if not runs:
        return None
    own = dict(trace["ops"])            # seconds less what runs inside
    for name, seconds in trace["ops"].items():
        row = table.get(name)
        if row and row["inside"] in own:
            own[row["inside"]] -= seconds

    def named(name):
        while name is not None:
            row = table[name]
            if "/" in row["op_name"]:
                return row["op_name"]
            name = row["inside"]
        return ""

    ms = 1e3 / runs
    out = {"runs_a_chip": runs, "step_ms": ms * sum(own.values()),
           "operations": len(own), "matched_ms": 0.0, "unattributed_ms": 0.0,
           "outside_blocks_ms": 0.0, "inside_switches_ms": 0.0,
           "by_transform": dict.fromkeys(TRANSFORMS, 0.0),
           "by_kind": {}, "by_conv_kind": {}, "by_layer": {},
           "by_scope": {}, "top_operations": []}
    ranked = []

    def add(group, key, transform, value):
        row = out[group].setdefault(key, dict.fromkeys(TRANSFORMS, 0.0))
        row[transform] += value

    for name, seconds in own.items():
        value = ms * seconds
        if name not in table:
            out["unattributed_ms"] += value
            continue
        out["matched_ms"] += value
        if table[name]["inside"] is not None:
            out["inside_switches_ms"] += value
        op_name = named(name)
        transform = xprof.transform_of(op_name)
        if transform is None:
            out["unattributed_ms"] += value
            continue
        out["by_transform"][transform] += value
        scopes = scopes_of(xprof.scope_path(op_name))
        label = label_of(scopes)
        add("by_scope", label, transform, value)
        ranked.append((value, name, table[name]["opcode"], transform, label))
        if transform == "optimizer":
            continue
        if not any(is_block(w) for w in scopes):
            out["outside_blocks_ms"] += value
        kind, layer = kind_of(scopes)
        add("by_kind", kind, transform, value)
        add("by_conv_kind", conv_kind_of(scopes), transform, value)
        if layer is not None:
            out["by_layer"].setdefault(str(layer), dict.fromkeys(KINDS, 0.0))[
                kind] += value
    if not out["matched_ms"]:
        return None
    # what ``fusion.1364`` is: the longest operations by their own names
    out["top_operations"] = [
        {"operation": name, "opcode": opcode, "ms": value,
         "transform": transform, "scope": label}
        for value, name, opcode, transform, label in heapq.nlargest(
            TOP, ranked)]
    whole = out["step_ms"]
    out["matched_pct"] = 100.0 * out["matched_ms"] / whole
    out["unattributed_pct"] = 100.0 * out["unattributed_ms"] / whole
    out["outside_blocks_pct"] = 100.0 * out["outside_blocks_ms"] / whole
    return out


def matrix(ctx):
    """The run's matrix, joined at the first reader's request and kept in
    ``ctx``; written beside the trace's operation table."""
    if "_step_scopes" not in ctx:
        ctx["_step_scopes"] = _joined(ctx)
    return ctx["_step_scopes"]


def _joined(ctx):
    # the parent of the PR that brought the table has no such function
    operations = getattr(xprof, "step_operations", None)
    table = operations() if operations and ctx.get("trace") else None
    found = join(ctx["trace"], table) if table else None
    if found is not None:
        cell = ctx["cell"]
        found["cell"] = cell.name
        # what the parse cost, after the window and outside every metric
        found["table"] = xprof.ledger("parallel.train_step",
                                      resolve=False)[-1].get("operations")
        os.makedirs(cell.out_dir, exist_ok=True)
        with open(os.path.join(cell.out_dir, "step_by_scope.json"),
                  "w") as f:
            json.dump(found, f, indent=1, sort_keys=True)
    return found


def transform_ms(ctx, transform):
    found = matrix(ctx)
    return None if found is None else found["by_transform"][transform]


def kind_ms(ctx, kind, group="by_kind"):
    """A kind's milliseconds over forward, recomputed and backward; 0.0 in
    a step that has none of it."""
    found = matrix(ctx)
    if found is None:
        return None
    return sum(found[group].get(kind, {}).values())
