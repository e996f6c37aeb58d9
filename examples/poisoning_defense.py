"""Poisoning-attack defense demo, at paper scale and engine scale.

Default (the paper's §IV.A setup): two of 12 robots flip 60% of their
labels; FoolsGold similarity re-weighting + the deviation ban keep the
global model clean, disabling both lets the attack degrade accuracy.

``--clients N`` (> 12) switches to the engine-scale story: a tiled
homogeneous fleet where 25% of the clients form a replica sybil clique
(one poisoned shard duplicated across identities — the Fung et al. threat
model).  There the dense statistic misfires on honest look-alikes, so the
default strategy becomes the cluster-aware ``foolsgold_sketch``
(``--defense`` overrides).  ``--devices k`` runs the round loop sharded
over k client shards; the defense then gathers only the (N, r) sketch.
``--dataset`` swaps the sample pool the fleets draw from: the default
deterministic synthetic digits, or real ``mnist`` / ``emnist`` IDX files
from the local cache dir (offline synthetic fallback when uncached — the
attack geometry is identical either way).

Run:  PYTHONPATH=src python examples/poisoning_defense.py
      PYTHONPATH=src python examples/poisoning_defense.py --clients 128
      PYTHONPATH=src python examples/poisoning_defense.py \
          --clients 64 --devices 8 --rounds 3 --samples 60
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--samples", type=int, default=300,
                    help="samples per client")
    ap.add_argument("--defense", default=None,
                    choices=["none", "foolsgold", "foolsgold_sketch"],
                    help="defense strategy (default: foolsgold at 12 "
                         "robots, foolsgold_sketch at engine scale)")
    ap.add_argument("--devices", type=int, default=1,
                    help="client shards; >1 runs the mesh-sharded engine")
    ap.add_argument("--dataset", default="synthetic",
                    choices=["synthetic", "mnist", "emnist"],
                    help="sample pool for the fleets (cached IDX files or "
                         "the deterministic offline fallback)")
    ap.add_argument("--compress", default="none",
                    choices=["none", "qsgd", "topk"],
                    help="uplink delta compression with error feedback; "
                         "both the defended and undefended runs use it, so "
                         "the comparison stays apples-to-apples")
    ap.add_argument("--compress_bits", type=int, default=8,
                    choices=[4, 8],
                    help="qsgd quantization width (bits per coordinate)")
    ap.add_argument("--compress_k", type=int, default=None,
                    help="topk coordinates kept per client "
                         "(default: model_dim // 32)")
    ap.add_argument("--faults", default="none",
                    choices=["none", "crash", "corrupt", "battery",
                             "flaky", "chaos"],
                    help="deterministic fault injection (core/faults.py) "
                         "layered on top of the poisoning attack; both "
                         "runs inject the identical schedule, so the "
                         "defended-vs-undefended gap isolates the defense")
    ap.add_argument("--fault_rate", type=float, default=None,
                    help="override the per-round crash AND corrupt-emission "
                         "probabilities of the chosen fault schedule")
    ap.add_argument("--cache_dir", default=None,
                    help="IDX cache dir for mnist/emnist (default: "
                         "$FEDAR_DATA_DIR or ~/.cache/fedar)")
    args = ap.parse_args()

    if args.clients != 12 and args.clients < 64:
        # the cluster-aware statistic fires on cliques that outgrow the
        # fleet's natural cluster scale (slack * median multiplicity); a
        # 25% clique of a tiny fleet stays inside it and the demo would
        # show nothing
        ap.error("engine-scale demo needs --clients >= 64 (a N/4 replica "
                 "clique below that is within the natural cluster scale "
                 "and is not down-weighted)")
    if args.devices > 1:
        if args.clients % args.devices:
            ap.error(f"--clients {args.clients} must divide by "
                     f"--devices {args.devices}")
        # must land before jax initializes its backends
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax.numpy as jnp
    import numpy as np

    from repro.common.compile_cache import enable_compile_cache
    from repro.configs.fedar_mnist import MnistConfig, fleet_fed
    from repro.core.fedar import FedARServer
    from repro.core.resources import TaskRequirement
    from repro.data.federated import sybil_fleet, table2_fleet
    from repro.data.sources import eval_source, get_source

    enable_compile_cache()
    paper_scale = args.clients == 12
    mesh = args.devices if args.devices > 1 else None
    source = get_source(args.dataset, cache_dir=args.cache_dir)
    if source.fallback:
        print(f"[data] {args.dataset}: no IDX files cached — deterministic "
              "synthetic fallback")
    # held-out eval split, loaded once and shared by both runs
    eval_src, warn = eval_source(args.dataset, source.fallback,
                                 cache_dir=args.cache_dir)
    if warn:
        print(warn)
    ex, ey = eval_src.sample(500, seed=99)

    compress_kw = dict(compress=args.compress,
                       compress_bits=args.compress_bits,
                       compress_k=args.compress_k)
    faults_kw = dict(faults=args.faults)
    if args.fault_rate is not None:
        faults_kw.update(fault_crash_rate=args.fault_rate,
                         fault_corrupt_rate=args.fault_rate)

    def run(defense: str):
        if paper_scale:
            fed = fleet_fed(
                12, local_epochs=3, timeout=30.0, defense=defense,
                deviation_gamma=2.5 if defense != "none" else 1e9,
                mesh_shape=mesh, **compress_kw, **faults_kw,
            )
            data = table2_fleet(samples_per_client=args.samples,
                                flip_frac=0.8, source=source)
            sybils = np.zeros(12, bool)
            sybils[10:] = True
        else:
            n_syb = args.clients // 4
            fed = fleet_fed(
                args.clients, local_epochs=2, defense=defense,
                num_poisoners=n_syb, num_starved=0, client_fraction=1.0,
                deviation_gamma=1e9,  # isolate the similarity defense
                mesh_shape=mesh, **compress_kw, **faults_kw,
            )
            data, sybils = sybil_fleet(args.clients, n_syb,
                                       samples_per_client=args.samples,
                                       source=source)
        srv = FedARServer(MnistConfig(), fed, TaskRequirement())
        data = {k: jnp.asarray(v) for k, v in data.items()}
        hist = srv.run(data, rounds=args.rounds, eval_set=(ex, ey))
        fgw = None
        if defense != "none" and not paper_scale:
            # engine scale: report the per-client defense weights over the
            # final history (paper scale catches its 2 independent flippers
            # via the deviation ban, not the similarity statistic)
            fgw = np.asarray(srv.engine.defense.weights(
                srv.state.fg_history, jnp.ones(args.clients, bool)
            ))
        return hist, fgw, sybils

    defense = args.defense or ("foolsgold" if paper_scale
                               else "foolsgold_sketch")
    print(f"defended ({defense}"
          + (" + deviation ban):" if paper_scale else "):"))
    h1, fgw, sybils = run(defense)
    print("  acc:", [round(a, 3) for a in h1["acc"]])
    if fgw is not None:
        print(f"  defense weights: sybil max {fgw[sybils].max():.3f}  "
              f"honest min {fgw[~sybils].min():.3f}")
    print("undefended:")
    h0, _, _ = run("none")
    print("  acc:", [round(a, 3) for a in h0["acc"]])
    print(f"\nfinal: defended {h1['acc'][-1]:.3f} "
          f"vs undefended {h0['acc'][-1]:.3f}")


if __name__ == "__main__":
    main()
