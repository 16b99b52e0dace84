"""Record tests/data/serving_golden_trace.json, and replay it.

The golden trace pins the DEFAULT scheduler policy's behaviour: scripted
traffic that exercises all four scheduling decisions — staggered FIFO
admission, recompute preemption under a withheld (tight) page pool,
prefill bucketing across mixed prompt lengths, and {1, decode_burst}
burst sizing — and the token streams a fresh engine produced for it.
tests/test_scheduler_policy.py replays it through `replay()` below and
demands the same streams, token for token.

The streams are a function of the weights `paddle.seed(seed)` draws, and
those are a function of jax's random streams, so the file names the jax it
was recorded under and must be re-recorded when that changes. Before it
writes anything, this script shows that what it records is the ENGINE's
doing and not an accident of one code path: every greedy request's stream
must equal `model.generate()` — the dense, non-paged, non-batched decode —
on the same weights.

    JAX_PLATFORMS=cpu python tools/capture_serving_golden_trace.py
    JAX_PLATFORMS=cpu python tools/capture_serving_golden_trace.py --check
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
GOLDEN = os.path.join(REPO, "tests", "data", "serving_golden_trace.json")

MODEL = {"seed": 0, "vocab": 97, "hidden": 32, "layers": 2, "heads": 4,
         "seq": 64}
# the late requests join after the second step: admission is staggered
EARLY = 5
SAMPLING = dict(decode_strategy="sampling", temperature=0.8, top_k=8,
                top_p=0.9)


def scenario_inputs():
    """The scripted traffic: eight prompts of mixed lengths and budgets,
    under four engine configurations."""
    prompts = [[47, 68, 25, 67], [83, 23, 92, 57, 14, 23],
               [72, 89, 42, 90, 8], [39, 68, 48, 7, 44, 0, 75],
               [55, 6, 19], [60, 44, 63, 69, 56, 24, 55, 53, 61],
               [64, 34, 56, 73, 78, 38], [4, 9, 87, 67]]
    budgets = [1, 3, 9, 4, 12, 6, 2, 8]
    base = dict(max_batch=4, max_seq_len=32, page_size=8, decode_burst=1)

    def sc(engine, withhold=0, sampling=()):
        return {"engine": engine, "prompts": prompts, "budgets": budgets,
                "withhold_pages": withhold,
                "sampling_rows": list(sampling)}

    return {
        "single_step": sc(base),
        "burst4": sc({**base, "decode_burst": 4}),
        # 4-token pages with most of the pool withheld: decode growth
        # runs out of pages and preempts the youngest slot
        "preempt": sc({**base, "page_size": 4}, withhold=22),
        "mixed_sampling": sc(base, sampling=(1, 4)),
    }


def tiny_model(model_cfg=None):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    mc = model_cfg or MODEL
    paddle.seed(mc["seed"])
    cfg = LlamaConfig.tiny(vocab=mc["vocab"], hidden=mc["hidden"],
                           layers=mc["layers"], heads=mc["heads"],
                           seq=mc["seq"])
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def replay(sc, model, scheduler=None):
    """Drive a fresh engine through a scenario's scripted traffic and
    return (per-request outputs in request order, preemption count)."""
    from paddle_tpu.inference import ServingEngine

    eng = ServingEngine(model, decode_strategy="greedy_search", seed=0,
                        scheduler=scheduler, **sc["engine"])
    # the preemption counter lives in the process-wide default registry —
    # other engines share it, so count the DELTA
    preempt0 = int(eng._m.preemptions.value)
    if sc["withhold_pages"]:
        eng._free_pages = eng._free_pages[:-sc["withhold_pages"]]
    sampling_rows = set(sc["sampling_rows"])
    rids, finished = [], {}

    def add(i):
        extra = SAMPLING if i in sampling_rows else {}
        rids.append(eng.add_request(
            np.asarray(sc["prompts"][i], np.int64),
            max_new_tokens=sc["budgets"][i], **extra))

    for i in range(EARLY):
        add(i)
    late = list(range(EARLY, len(sc["prompts"])))
    steps = 0
    while eng.has_work() and steps < 500:
        for fin in eng.step():
            finished[fin.request_id] = fin.output_ids.tolist()
        steps += 1
        if steps == 2 and late:
            for i in late:
                add(i)
            late = []
    if len(finished) != len(rids):
        raise RuntimeError(f"{len(finished)} of {len(rids)} requests "
                           f"finished in {steps} steps")
    return [finished[r] for r in rids], \
        int(eng._m.preemptions.value) - preempt0


def generate_streams(sc, model):
    """The same requests through model.generate(), one at a time, greedy:
    the reference the engine's greedy streams must equal."""
    out = {}
    for i, (p, b) in enumerate(zip(sc["prompts"], sc["budgets"])):
        if i in sc["sampling_rows"]:
            continue
        new, _ = model.generate(np.asarray(p, np.int64)[None],
                                max_new_tokens=b,
                                decode_strategy="greedy_search")
        out[i] = np.asarray(new._data)[0].tolist()
    return out


def capture():
    import jax

    trace = {"jax_version": jax.__version__,
             "captured_by": "tools/capture_serving_golden_trace.py",
             "model": MODEL, "scenarios": {}}
    for name, sc in scenario_inputs().items():
        outputs, preemptions = replay(sc, tiny_model())
        ref = generate_streams(sc, tiny_model())
        bad = [i for i, want in ref.items() if outputs[i] != want]
        if bad:
            raise SystemExit(
                f"{name}: engine streams of greedy requests {bad} differ "
                f"from model.generate() on the same weights — that is an "
                f"engine defect, not a weight-stream change; nothing "
                f"recorded")
        print(f"{name}: {len(ref)} greedy streams equal model.generate(); "
              f"{preemptions} preemption(s)")
        trace["scenarios"][name] = {**sc, "outputs": outputs,
                                    "preemptions": preemptions}
    if not any(s["preemptions"] for s in trace["scenarios"].values()):
        raise SystemExit("no scenario preempted: the victim decision "
                         "would go unrecorded")
    return trace


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="capture, compare with the committed file, "
                         "write nothing")
    args = ap.parse_args(argv)
    trace = capture()
    if args.check:
        with open(GOLDEN) as f:
            committed = json.load(f)
        same = committed.get("scenarios") == trace["scenarios"]
        print(f"committed trace (jax {committed.get('jax_version')}) "
              f"{'matches' if same else 'DIFFERS from'} this capture "
              f"(jax {trace['jax_version']})")
        return 0 if same else 1
    with open(GOLDEN, "w") as f:
        json.dump(trace, f, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
