"""Calls of ``gluon.loss.SoftmaxCrossEntropyLoss`` that the program traced
on the path that writes ``log_softmax(pred)`` as an array of the logits'
size (its ``loss.softmax_ce.materialized`` counter, at trace time): a
second [tokens, vocabulary] array and, from sparse labels, a gather of one
element a row from it. 0 is the number to expect: sparse labels take one
float32 pass over the logits (``loss.softmax_ce.one_pass``). A program
with neither counter has nothing to read."""


def read(ctx):
    if not ctx["window"].get("attempted"):
        return None
    from mxtpu import telemetry
    if not (telemetry.value("loss.softmax_ce.one_pass")
            or telemetry.value("loss.softmax_ce.materialized")):
        return None
    return telemetry.value("loss.softmax_ce.materialized")
