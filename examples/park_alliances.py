"""Park, Chen & Szolnoki (2023) eight-species alliances (paper §4.3.2,
Figs 4.8-4.13) + the Cliff & Sinadjan mobility extension (Appendix C).

    PYTHONPATH=src python examples/park_alliances.py \
        --alpha 0.15 --beta 0.75 --L 48 --trials 8
    PYTHONPATH=src python examples/park_alliances.py \
        --alpha 0.15 0.6 --beta 0.75 0.25 --L 48 --trials 8
    PYTHONPATH=src python examples/park_alliances.py --mobility 1e-4 ...

Reports per-species survival probabilities and the survivor-count
histogram over vmapped IID trials; with --mobility > 0 it reproduces the
companion paper's central claim that mobility changes the phase behaviour.
Several --alpha/--beta values name (alpha, beta) pairs, taken in order,
which run as one batch of pairs x trials lattices with one compiled
program (``park.survival_probabilities_sweep``).
"""
import argparse

from repro.core.park import survival_probabilities_sweep


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--alpha", type=float, nargs="+", default=[0.15])
    ap.add_argument("--beta", type=float, nargs="+", default=[0.75])
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--L", type=int, default=48)
    ap.add_argument("--trials", type=int, default=8)
    ap.add_argument("--mcs", type=int, default=0,
                    help="0 -> Park protocol (L^2)")
    ap.add_argument("--mobility", type=float, default=0.0,
                    help=">0 enables the companion-paper extension")
    args = ap.parse_args()
    if len(args.alpha) != len(args.beta):
        ap.error("give as many --beta values as --alpha values: "
                 "each pair is one point of the sweep")

    mcs = args.mcs or args.L * args.L
    points = list(zip(args.alpha, args.beta))
    results = survival_probabilities_sweep(
        points, args.gamma, L=args.L, n_trials=args.trials, mcs=mcs,
        mobility=args.mobility)

    for (alpha, beta), (ps, hist) in zip(points, results):
        tag = (f"alpha={alpha} beta={beta} gamma={args.gamma} "
               f"L={args.L} mcs={mcs} mobility={args.mobility}")
        print(f"Park alliances: {tag}")
        print("survival probability per species:")
        for i, p in enumerate(ps, start=1):
            bar = "#" * int(p * 40)
            print(f"  s{i}: {p:5.2f} {bar}")
        print("survivor-count histogram:",
              " ".join(f"{i}:{v:.2f}" for i, v in enumerate(hist) if v > 0))
        print(f"species-5 extinction probability: {1 - ps[4]:.3f} "
              f"(paper Fig 4.11-4.13 studies this across alpha)")
    if args.mobility > 0:
        print("mobility > 0: the companion paper shows this collapses "
              "Park et al.'s phase structure — compare against "
              "--mobility 0 at the same seed")


if __name__ == "__main__":
    main()
