"""Serving example: continuous-batching greedy decode across architectures,
including the attention-free and hybrid families.  Each arch runs through
``repro.serve.ContinuousBatcher`` (slot-pool decode, requests join/leave at
decode-step granularity); pass ``--trace N`` to replay a synthetic
open-loop arrival trace instead of one gang batch.

    PYTHONPATH=src python examples/serve_lm.py [--arch mamba2-1.3b]
    PYTHONPATH=src python examples/serve_lm.py --trace 6
"""
import argparse
import sys

sys.path.insert(0, "src")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="one arch id; default: a representative trio")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--trace", type=int, default=0,
                    help="serve N staggered arrivals (open-loop trace)")
    args = ap.parse_args()

    from repro.launch.serve import main as serve_main

    archs = ([args.arch] if args.arch else
             ["granite-3-2b", "mamba2-1.3b", "recurrentgemma-2b"])
    for arch in archs:
        flags = ["--arch", arch, "--reduced", "--batch", str(args.batch),
                 "--prompt-len", "32", "--gen", str(args.gen)]
        if args.trace:
            flags += ["--trace", str(args.trace)]
        serve_main(flags)


if __name__ == "__main__":
    main()
