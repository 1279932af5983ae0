"""Quickstart: the paper's technique in ~50 lines, plus the codec axis.

Runs federated collaborative filtering on a synthetic Movielens-like
dataset four ways — full payload (FCF), bandit-selected 10% payload
(FCF-BTS, the paper's method), random 10% payload (FCF-Random), and
FCF-BTS with the 10% payload *also* quantized to int8 on the wire
(the compression subsystem's joint rows x bits reduction) — then prints
recommendation quality next to the bytes actually moved.

  PYTHONPATH=src python examples/quickstart.py

Fault-tolerance flags (docs/FAULT_MODEL.md) drive the crash-resume
contract end to end: `--checkpoint-dir` checkpoints at eval boundaries,
`--crash-round T` simulates a host crash at round T (the process exits
via SimulatedCrash), and a second invocation with `--resume-from DIR`
picks up from the newest hash-verified checkpoint and finishes with the
exact trajectory the uninterrupted run would have had.
"""
import argparse
from typing import Optional, Sequence

from repro.data.synthetic import load_dataset
from repro.faults import FaultConfig, SimulatedCrash
from repro.federated.simulation import FLSimConfig, run_fcf_simulation


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint the BTS run at every eval boundary")
    ap.add_argument("--crash-round", type=int, default=None,
                    help="simulate a host crash at this round (BTS run)")
    ap.add_argument("--resume-from", default=None,
                    help="resume the BTS run from a checkpoint dir/path")
    args = ap.parse_args(argv)
    fault_kw = {}
    if args.crash_round is not None:
        fault_kw["faults"] = FaultConfig(enabled=True,
                                         crash_round=args.crash_round)

    spec, train, test = load_dataset("movielens-mini", seed=0)
    print(f"dataset: {spec.name}  users={spec.num_users} items={spec.num_items}")

    variants = {
        "full": dict(strategy="full"),
        # the bts run is the one the fault-tolerance flags drive
        "bts": dict(strategy="bts", checkpoint_dir=args.checkpoint_dir,
                    resume_from=args.resume_from, **fault_kw),
        "random": dict(strategy="random"),
        "bts+int8": dict(strategy="bts", codec="int8"),
    }
    results = {}
    for name, kw in variants.items():
        cfg = FLSimConfig(keep_fraction=0.10, rounds=args.rounds, theta=50,
                          eval_every=max(args.rounds // 6, 1),
                          eval_users=200, seed=0, **kw)
        try:
            results[name] = run_fcf_simulation(train, test, cfg)
        except SimulatedCrash as exc:
            print(f"\nsimulated crash at round {exc.round_} — rerun with "
                  f"--resume-from {args.checkpoint_dir} to continue")
            raise SystemExit(3)

    print(f"\n{'method':<12} {'F1@10':>8} {'MAP@10':>8} {'MB moved':>10}")
    for name, res in results.items():
        mb = (res.bytes_down + res.bytes_up) / 1e6
        print(f"{name:<12} {res.final['f1']:>8.4f} "
              f"{res.final['map']:>8.4f} {mb:>10.1f}")

    full, bts = results["full"], results["bts"]

    def moved(r):
        return r.bytes_down + r.bytes_up

    saved = 100 * (1 - moved(bts) / moved(full))
    drop = 100 * (1 - bts.final["f1"] / full.final["f1"])
    print(f"\nFCF-BTS moved {saved:.0f}% fewer bytes for a "
          f"{drop:.1f}% F1 drop (paper: 90% fewer, ~4-8% drop on sparse data)")

    q = results["bts+int8"]
    saved_q = 100 * (1 - moved(q) / moved(full))
    drop_q = 100 * (1 - q.final["f1"] / full.final["f1"])
    print(f"BTS + int8 wire moved {saved_q:.1f}% fewer bytes "
          f"({moved(bts) / moved(q):.1f}x less than BTS alone) for a "
          f"{drop_q:.1f}% F1 drop — the second payload axis is almost free")


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
