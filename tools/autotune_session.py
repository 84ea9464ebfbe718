#!/usr/bin/env python
"""Run measured Pallas block-plan searches from the ledger's work order.

The closing arc of the observe → tune → persist → serve loop
(docs/autotune.md): ``telemetry_report.py --tuning-queue`` ranks the
memory-bound jit sites by executed FLOPs; this CLI consumes that queue
top-down, maps each site onto the registered tunable kernels, and runs
:func:`mxtpu.ops.pallas.autotune.search` over each kernel's declared
representative shape classes. Winning plans are installed AND persisted
under ``MXTPU_COMPILE_CACHE_DIR`` (set it, or the session tunes into
thin air), so the NEXT process — a restarted trainer, a fresh replica —
serves them with zero warm-start searches.

The queue carries jit *sites* (e.g. ``trainer.step``) while plans key on
kernel *shape classes*; the mapping is deliberately honest: a queue
entry establishes that tuning a kernel family is warranted and in what
order, and the shape classes swept are the family's own declared
representatives (``TunableKernel.classes``), scaled down on the host
tier so interpret-mode candidates stay inside a CI budget.

One JSON line per search on stdout (kernel, class, default vs best plan,
speedup, persisted path) and a final ``AUTOTUNE_SESSION`` summary line —
the perf-battery artifact grammar.

Usage::

    python tools/autotune_session.py [--queue tuning_queue.json]
        [--kernels pallas_conv,pallas_flash] [--budget-s S] [--rounds N]
        [--limit K]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# site keywords -> kernel family, for ordering kernels by the queue's
# ranked sites; an unmatched site leaves the registry order untouched
_SITE_HINTS = (("conv", "pallas_conv"), ("stem", "pallas_conv"),
               ("resnet", "pallas_conv"), ("attention", "pallas_flash"),
               ("flash", "pallas_flash"), ("transformer", "pallas_flash"))


def _kernel_order(queue, registered):
    """Registered kernel ids, queue-ranked first. The queue's top site
    pulls its kernel family to the front; families the queue never
    mentions keep registry order at the back."""
    ranked = []
    for entry in queue:
        site = str(entry.get("site", "")).lower()
        for word, kid in _SITE_HINTS:
            if word in site and kid in registered and kid not in ranked:
                ranked.append(kid)
    return ranked + [k for k in sorted(registered) if k not in ranked]


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="measured Pallas block-plan tuning session")
    ap.add_argument("--queue", default=None,
                    help="tuning_queue.json from telemetry_report.py "
                         "--tuning-queue (orders the kernel families)")
    ap.add_argument("--kernels", default=None,
                    help="comma-separated kernel ids (default: all "
                         "registered, queue-ranked)")
    ap.add_argument("--budget-s", type=float, default=None,
                    help="wall budget per search (default "
                         "MXTPU_AUTOTUNE_BUDGET_S or 30)")
    ap.add_argument("--rounds", type=int, default=None,
                    help="timed rounds per candidate (default "
                         "MXTPU_AUTOTUNE_ROUNDS or 3)")
    ap.add_argument("--limit", type=int, default=None,
                    help="max searches this session (bounds CI time)")
    args = ap.parse_args(argv)

    from mxtpu.ops.pallas import autotune
    from mxtpu.ops.pallas.flash_attention import _platform

    queue = []
    if args.queue:
        with open(args.queue, encoding="utf-8") as f:
            doc = json.load(f)
        if doc.get("format") != 1:
            print("unsupported tuning-queue format: %r"
                  % doc.get("format"), file=sys.stderr)
            return 1
        queue = doc.get("queue") or []

    registered = autotune.kernels()
    if args.kernels:
        kids = [k.strip() for k in args.kernels.split(",") if k.strip()]
        unknown = [k for k in kids if k not in registered]
        if unknown:
            print("unknown kernel id(s): %s (registered: %s)"
                  % (", ".join(unknown), ", ".join(sorted(registered))),
                  file=sys.stderr)
            return 1
    else:
        kids = _kernel_order(queue, registered)

    if not os.environ.get("MXTPU_COMPILE_CACHE_DIR"):
        print("warning: MXTPU_COMPILE_CACHE_DIR is unset — winning "
              "plans will be installed in-process but NOT persisted",
              file=sys.stderr)

    host_tier = _platform() != "tpu"
    ran = improved = 0
    for kid in kids:
        tk = registered[kid]
        for sc in tk.classes(host_tier):
            if args.limit is not None and ran >= args.limit:
                break
            res = autotune.search(kid, sc, rounds=args.rounds,
                                  budget_s=args.budget_s)
            ran += 1
            improved += int(res["improved"])
            line = {k: res[k] for k in
                    ("kernel", "class", "device", "candidates", "timed",
                     "budget_exhausted", "default_plan_id", "default_s",
                     "best_plan_id", "best_s", "speedup_vs_default",
                     "improved", "persisted")}
            print(json.dumps(line, sort_keys=True), flush=True)
    print("AUTOTUNE_SESSION " + json.dumps(
        {"searches": ran, "improved": improved,
         "host_tier": host_tier,
         "queue_sites": len(queue),
         "kernels": kids,
         "cache_dir": os.environ.get("MXTPU_COMPILE_CACHE_DIR")},
        sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    from perf_common import use_xla_cache
    use_xla_cache()
    sys.exit(main())
