"""ptreplay: re-drive a recorded serving workload and prove the tokens.

The record half (paddle_tpu/serving/replay.py, FLAGS_serving_replay)
journals every request an engine serves — prompt ids, sampling params,
the engine's latched flag snapshot, weights generation, and the output
token digest. This tool is the replay half: it rebuilds a REAL engine
(same model ctor path the benchmarks use — config kwargs + init seed
from the journal header's ``model`` meta), re-drives the journal
through it, and diffs token-for-token. Greedy decode is deterministic
per slot and the engine compiles ONE decode step, so replay costs no
recompiles (``decode_compiles == 1`` is re-checked here) and batching
order cannot change outputs.

Modes:
  run <journal>            digest-only divergence report (rolling token
                           hash per request); rc=2 on any divergence,
                           rc=4 if replay broke compile-once
    --full                 token-level diff: first diverging index +
                           both token tails per diverging request
    --matrix               replay across the prefix x chunked x
                           quant_kv x quant_weights flag matrix and
                           BISECT which axis introduces divergence: a
                           baseline (recorded-flags) divergence names
                           the ``weights`` axis (re-execution itself
                           disagrees — a perturbed/hot-swapped leaf),
                           a clean baseline with a diverging flip
                           names that flag axis
    --against <journal2>   diff two recordings pairwise (the canary
                           story: record on weights generation N,
                           record on N+1, diff — no engine rebuilt)
  smoke                    host-only CPU self-check: record a mixed
                           tiny workload (prefix
                           hits + chunked prefill + quant-kv +
                           forced preempt/resume), require a
                           zero-divergence identity replay with
                           decode_compiles == 1, prove detection power
                           on a deliberately perturbed weight leaf
                           (and that --matrix names ``weights``), and
                           commit tools/replay_snapshot.json (rc=3 and
                           nothing written when the smoke cannot run)

Divergences count into ``replay_divergences_total{axis}`` and open a
``replay_divergence`` incident (evidence: the report path) when the
incident plane is on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SMOKE_OUT = os.path.join(os.path.dirname(__file__),
                         "replay_snapshot.json")


def _first_divergence(a, b):
    """Index of the first differing token, or None if identical."""
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    if len(a) != len(b):
        return n
    return None


def _build_model(model_meta):
    """The recording side's model ctor path: seed, then config kwargs.
    The seed reset makes weight init bit-reproducible — replay's whole
    premise."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if not model_meta or "config" not in model_meta:
        raise SystemExit(
            "journal carries no model meta (note_model() a "
            "{'config': {...}, 'seed': N} block before write_journal)")
    paddle.seed(int(model_meta.get("seed", 0)))
    cfg = LlamaConfig(use_parallel=False, **model_meta["config"])
    return LlamaForCausalLM(cfg)


def _perturb_one_leaf(model, scale=1.5):
    """Scale ONE projection weight leaf in place — the deliberate
    divergence the smoke row uses to prove the replay check has
    detection power (the ptcheck expected-finding discipline: a
    checker that cannot fail a broken run proves nothing)."""
    for name, p in model.named_parameters():
        v = p._value
        if getattr(v, "ndim", 0) == 2:
            p._value = v * scale
            return name
    raise RuntimeError("no 2-D weight leaf to perturb")


def replay_entries(head, entries, flags_override=None, full=False,
                   perturb=False):
    """Re-drive every finished entry through freshly built engines
    (one per recorded engine id, flags latched from the journal unless
    overridden) and return the divergence report block."""
    from paddle_tpu import serving
    from paddle_tpu.core import flags as ptflags

    replayable = [e for e in entries if e.get("state") == "finished"]
    skipped = {}
    for e in entries:
        if e.get("state") != "finished":
            skipped[e.get("state")] = skipped.get(e.get("state"), 0) + 1

    by_engine = {}
    for e in replayable:
        by_engine.setdefault(str(e.get("engine", 0)), []).append(e)

    divergences = []
    compiles = {}
    perturbed_leaf = None
    for eid, group in sorted(by_engine.items()):
        snap = (head.get("engines") or {}).get(eid) or {}
        flags = dict(snap.get("flags") or group[0]["flags"])
        if flags_override:
            flags.update(flags_override)
        caps = snap.get("caps") or {}
        # flags latch at Engine construction (PR-9): set BEFORE build
        ptflags.set_flags(flags)
        model = _build_model(head.get("model"))
        if perturb:
            perturbed_leaf = _perturb_one_leaf(model)
        eng = serving.Engine(
            model,
            max_slots=int(caps.get("max_slots", 4)),
            num_blocks=int(caps.get("num_blocks", 128)),
            block_size=int(caps.get("block_size", 16)),
            prefill_chunk=int(caps.get("prefill_chunk", 16)),
            max_model_len=caps.get("max_model_len"))
        rid_of = {}
        for e in group:
            # no deadline: replay determinism must not depend on the
            # replaying host's wall-clock speed
            rid = eng.add_request(e["prompt"],
                                  max_new_tokens=e["max_new_tokens"],
                                  eos_token_id=e.get("eos_token_id"))
            rid_of[rid] = e
        eng.run()
        for rid, e in rid_of.items():
            got = eng.output(rid)
            from paddle_tpu.serving.replay import token_hash

            got_hash = token_hash(got)
            want_hash = e.get("output_token_hash") \
                or token_hash(e.get("output") or ())
            if got_hash == want_hash:
                continue
            row = {"id": e["id"], "trace_id": e.get("trace_id"),
                   "engine": eid,
                   "recorded_hash": want_hash,
                   "replayed_hash": got_hash,
                   "weights_generation": e.get("weights_generation"),
                   "first_divergence": _first_divergence(
                       e.get("output") or [], got)}
            if full:
                row["recorded_tokens"] = e.get("output")
                row["replayed_tokens"] = got
            divergences.append(row)
        compiles[eid] = eng.stats()["decode_compiles"]
    return {
        "replayed": len(replayable),
        "skipped": skipped,
        "divergence_count": len(divergences),
        "divergences": divergences,
        "decode_compiles": compiles,
        "compile_once_ok": all(c == 1 for c in compiles.values()),
        "perturbed_leaf": perturbed_leaf,
    }


def matrix_bisect(head, entries, full=False, perturb=False):
    """Replay under the recorded flags, then once per flag axis with
    that ONE axis flipped. Bisection verdict: a baseline divergence
    names ``weights`` — the flags are identical to the recording, so
    re-execution itself disagrees, and the flag flips are skipped
    (every flip would inherit the same weight delta and prove
    nothing). A clean baseline with diverging flips names those flag
    axes; quant axes naming themselves is a finding about numerics
    (int8 KV / weight quantization are lossy), not a replay bug —
    only prefix and chunked are pinned token-identical by the repo's
    own tests."""
    from paddle_tpu.serving.replay import FLAG_AXES

    baseline = replay_entries(head, entries, full=full,
                              perturb=perturb)
    if baseline["divergence_count"]:
        return {
            "baseline_divergences": baseline["divergence_count"],
            "baseline": baseline,
            "axes": {},
            "bisected_axes": ["weights"],
        }
    axes = {}
    recorded_flags = {}
    for snap in (head.get("engines") or {}).values():
        recorded_flags.update(snap.get("flags") or {})
    for axis, flag in FLAG_AXES:
        flipped = not bool(recorded_flags.get(flag))
        res = replay_entries(head, entries,
                             flags_override={flag: flipped},
                             full=full, perturb=perturb)
        axes[axis] = {"flag": flag, "flipped_to": flipped,
                      "divergences": res["divergence_count"],
                      "compile_once_ok": res["compile_once_ok"]}
    return {
        "baseline_divergences": 0,
        "baseline": baseline,
        "axes": axes,
        "bisected_axes": [a for a, r in axes.items()
                          if r["divergences"]],
    }


def diff_journals(head_a, entries_a, head_b, entries_b, full=False):
    """Pairwise token diff of two recordings (--against): finished
    entries matched in admission order; a prompt mismatch marks the
    pair workload_mismatch instead of pretending it diverged."""
    fin_a = [e for e in entries_a if e.get("state") == "finished"]
    fin_b = [e for e in entries_b if e.get("state") == "finished"]
    pairs = min(len(fin_a), len(fin_b))
    divergences = []
    mismatches = 0
    for i in range(pairs):
        a, b = fin_a[i], fin_b[i]
        if a["prompt"] != b["prompt"] \
                or a["max_new_tokens"] != b["max_new_tokens"]:
            mismatches += 1
            continue
        if a.get("output_token_hash") == b.get("output_token_hash"):
            continue
        row = {"index": i, "id_a": a["id"], "id_b": b["id"],
               "hash_a": a.get("output_token_hash"),
               "hash_b": b.get("output_token_hash"),
               "weights_generation_a": a.get("weights_generation"),
               "weights_generation_b": b.get("weights_generation"),
               "first_divergence": _first_divergence(
                   a.get("output") or [], b.get("output") or [])}
        if full:
            row["tokens_a"] = a.get("output")
            row["tokens_b"] = b.get("output")
        divergences.append(row)
    return {
        "pairs": pairs,
        "unpaired": abs(len(fin_a) - len(fin_b)),
        "workload_mismatches": mismatches,
        "divergence_count": len(divergences),
        "divergences": divergences,
    }


def _note_divergences(report, out_path):
    """Feed the report's verdict into the metric/incident plane:
    replay_divergences_total{axis} + a replay_divergence incident with
    the report artifact as evidence."""
    from paddle_tpu.serving import replay as sreplay

    matrix = report.get("matrix")
    if matrix and matrix["bisected_axes"]:
        for axis in matrix["bisected_axes"]:
            n = (matrix["baseline_divergences"]
                 if axis == "weights"
                 else matrix["axes"][axis]["divergences"])
            sreplay.note_divergence(axis, max(n, 1), report=out_path)
    elif report.get("divergence_count"):
        sreplay.note_divergence("unknown", report["divergence_count"],
                                report=out_path)


def _write_report(path, report):
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, default=str)
        f.write("\n")
    os.replace(tmp, path)


def run_replay(args):
    import jax

    from paddle_tpu.serving import replay as sreplay

    head, entries = sreplay.load_journal(args.journal)
    report = {
        "kind": "replay_report",
        "version": 1,
        "journal": args.journal,
        "backend": jax.default_backend(),
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
        "recorded": head.get("requests"),
    }
    if args.against:
        head_b, entries_b = sreplay.load_journal(args.against)
        report["against"] = args.against
        report.update(diff_journals(head, entries, head_b, entries_b,
                                    full=args.full))
        compile_ok = True
    elif args.matrix:
        m = matrix_bisect(head, entries, full=args.full)
        report["matrix"] = m
        report["divergence_count"] = m["baseline_divergences"]
        report["divergences"] = m["baseline"]["divergences"]
        compile_ok = m["baseline"]["compile_once_ok"]
    else:
        report.update(replay_entries(head, entries, full=args.full))
        compile_ok = report["compile_once_ok"]
    _write_report(args.out, report)
    _note_divergences(report, args.out)
    print(json.dumps({k: v for k, v in report.items()
                      if k not in ("divergences", "matrix")}),
          flush=True)
    print("wrote", args.out, flush=True)
    if not compile_ok:
        sys.stderr.write("FAIL: replay broke compile-once "
                         "(decode_compiles != 1)\n")
        return 4
    if report.get("divergence_count"):
        axes = (report.get("matrix") or {}).get("bisected_axes")
        sys.stderr.write(
            "DIVERGED: %d request(s)%s — report: %s\n"
            % (report["divergence_count"],
               " (axes: %s)" % ",".join(axes) if axes else "",
               args.out))
        return 2
    return 0


def _smoke_record(tmpdir):
    """Record the smoke journal: tiny model, prefix + chunked +
    quant-kv on, shared-prefix prompts (cache hits) through a
    page-starved pool (forced preempt/resume) — the mixed workload
    the acceptance row names."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.core import flags as ptflags
    from paddle_tpu.serving import replay as sreplay

    model_meta = {
        "preset": "replay_smoke", "seed": 0,
        "config": dict(vocab_size=64, hidden_size=32,
                       intermediate_size=64, num_hidden_layers=2,
                       num_attention_heads=4,
                       max_position_embeddings=96),
    }
    ptflags.set_flags({
        "FLAGS_serving_replay": True,
        "FLAGS_serving_prefix_cache": True,
        "FLAGS_serving_chunked_prefill": True,
        "FLAGS_serving_quant_kv": True,
        "FLAGS_serving_quant_weights": False})
    sreplay.clear()          # fresh journal; Engine latch auto-enables
    model = _build_model(model_meta)
    # page-starved pool: concurrent slots contend for pages so some
    # requests preempt and resume (recompute path) mid-journal
    eng = serving.Engine(model, max_slots=4, num_blocks=10,
                         block_size=8, prefill_chunk=8)
    rng = np.random.RandomState(0)
    shared = rng.randint(0, 64, (16,)).tolist()
    for i in range(12):
        prompt = (shared + rng.randint(0, 64, (4 + i % 5,)).tolist()
                  if i % 2 else
                  rng.randint(0, 64, (6 + i % 7,)).tolist())
        eng.add_request(prompt, max_new_tokens=6 + i % 6)
    eng.run()
    sreplay.note_model(model_meta)
    journal = os.path.join(tmpdir, "replay_smoke.jsonl")
    sreplay.write_journal(journal)
    stats = eng.stats()
    record = {
        "requests": 12,
        "preemptions": stats["preemptions"],
        "prefix_hit_tokens": stats["prefix_hit_tokens"],
        "decode_compiles": stats["decode_compiles"],
    }
    # replay must not re-record: drop the plane back off before the
    # replay engines are built
    ptflags.set_flags({"FLAGS_serving_replay": False})
    sreplay.disable()
    return journal, record


def run_smoke(args):
    """Self-check: record -> identity replay -> perturbed detection ->
    matrix bisect, committed as one artifact. A smoke that fails to run
    writes nothing and returns 3; one that runs and finds a divergence
    writes its report and returns 2."""
    import tempfile

    import jax

    report = None
    try:
        from paddle_tpu.serving import replay as sreplay

        with tempfile.TemporaryDirectory() as td:
            journal, record = _smoke_record(td)
            head, entries = sreplay.load_journal(journal)
            identity = replay_entries(head, entries)
            perturbed = replay_entries(head, entries, perturb=True)
            matrix = matrix_bisect(head, entries)
            # the acceptance bisect: a replaying host whose weights
            # drifted must have the matrix name the weights axis, not
            # blame a flag
            matrix_perturbed = matrix_bisect(head, entries,
                                             perturb=True)
            report = {
                "kind": "replay_snapshot",
                "backend": jax.default_backend(),
                "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime()),
                "record": record,
                "identity": {
                    "divergences": identity["divergence_count"],
                    "replayed": identity["replayed"],
                    "compile_once_ok": identity["compile_once_ok"],
                },
                # detection power: a scaled weight leaf MUST diverge,
                # and the matrix MUST NOT blame a flag axis for it
                "perturbed": {
                    "leaf": perturbed["perturbed_leaf"],
                    "divergences": perturbed["divergence_count"],
                    "detected": perturbed["divergence_count"] > 0,
                },
                "matrix": {
                    "baseline_divergences":
                        matrix["baseline_divergences"],
                    "axes": {a: r["divergences"]
                             for a, r in matrix["axes"].items()},
                    "bisected_axes": matrix["bisected_axes"],
                },
                "matrix_perturbed": {
                    "bisected_axes": matrix_perturbed["bisected_axes"],
                },
            }
            # clean-journal matrix: baseline and the token-identity
            # axes (prefix, chunked) must not diverge; quant axes are
            # allowed to (lossy numerics is their finding to report).
            # perturbed-journal matrix: MUST bisect to weights.
            report["ok"] = bool(
                identity["divergence_count"] == 0
                and identity["compile_once_ok"]
                and record["preemptions"] > 0
                and record["prefix_hit_tokens"] > 0
                and report["perturbed"]["detected"]
                and matrix["baseline_divergences"] == 0
                and matrix["axes"]["prefix"]["divergences"] == 0
                and matrix["axes"]["chunked"]["divergences"] == 0
                and matrix_perturbed["bisected_axes"] == ["weights"])
    except Exception as e:
        sys.stderr.write("replay smoke failed: %r\n" % (e,))
        return 3
    _write_report(args.out, report)
    print(json.dumps(report), flush=True)
    print("wrote", args.out, flush=True)
    if not report["ok"]:
        return 2
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="deterministic serving record/replay audit")
    sub = ap.add_subparsers(dest="cmd", required=True)
    runp = sub.add_parser("run", help="replay a journal and diff")
    runp.add_argument("journal")
    runp.add_argument("--out", default="replay_report.json")
    runp.add_argument("--full", action="store_true",
                      help="token-level diff (first diverging index + "
                           "token tails), not just digests")
    runp.add_argument("--matrix", action="store_true",
                      help="replay across the flag matrix and bisect "
                           "the diverging axis")
    runp.add_argument("--against", default=None,
                      help="diff against a second journal instead of "
                           "re-executing (canary mode)")
    smokep = sub.add_parser("smoke", help="battery self-check row")
    smokep.add_argument("--out", default=SMOKE_OUT)
    args = ap.parse_args()
    if args.cmd == "smoke":
        return run_smoke(args)
    return run_replay(args)


if __name__ == "__main__":
    sys.exit(main())
