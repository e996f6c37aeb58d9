"""Federated LM through the one FedAR engine: transformer clients behind
``ClientModel``.

A fleet of robots each holding a topic-skewed slice of a synthetic corpus
(``corpus_skew``, the text analogue of label skew) trains a reduced
TinyLlama-family model through ``FedAREngine`` — the SAME engine the paper's
MNIST fleet runs: trust scoring, straggler masking, buffered async
aggregation (FedBuff-style) and the cluster-aware sketched FoolsGold defense
all apply unchanged, because the nested transformer param pytree crosses the
aggregation boundary through the engine's ``flatten``/``unflatten`` adapter.
Poisoner robots (paper fractions via ``make_fleet``) get their next-token
labels scrambled, so the defense has something real to catch.

``--devices k`` shards the round loop over k client shards (``shard_map``
over a ``clients`` mesh); on a CPU-only host it forces k fake host devices
via XLA_FLAGS, which is why jax is imported only after argument parsing.

Run:  PYTHONPATH=src python examples/federated_lm.py [--rounds 8]
      PYTHONPATH=src python examples/federated_lm.py --compare
      PYTHONPATH=src python examples/federated_lm.py --clients 16 --devices 4
"""
import argparse
import os
import time


def run(args, *, aggregation, defense, label):
    import jax.numpy as jnp
    import numpy as np

    from repro import FedARServer, LMClientModel, TaskRequirement
    from repro.configs import get_config
    from repro.configs.fedar_mnist import fleet_fed
    from repro.data.pipeline import federated_lm_corpus

    cfg = get_config(args.arch).reduced(
        num_layers=2, d_model=128, d_ff=256, vocab_size=512
    )
    model = LMClientModel(cfg)
    fed = fleet_fed(
        args.clients,
        local_epochs=2,
        local_batch_size=8,
        timeout=10.0,
        aggregation=aggregation,
        defense=defense,
        mesh_shape=args.devices if args.devices > 1 else None,
    )
    server = FedARServer(model, fed, TaskRequirement(), lr=args.lr)
    if server.mesh is not None:
        print(f"  mesh: {server.mesh.devices.size} client shards x "
              f"{args.clients // server.mesh.devices.size} clients")

    # align the data attack with the fleet's designated poisoner robots
    poisoners = tuple(int(i) for i in np.where(server.poison_mask)[0])
    data, meta = federated_lm_corpus(
        args.clients,
        vocab=cfg.vocab_size,
        seq=args.seq,
        samples_per_client=args.samples,
        topics=args.topics,
        poisoners=poisoners,
        seed=args.seed,
    )
    data = {k: jnp.asarray(v) for k, v in data.items()}
    eval_set = {k: jnp.asarray(v) for k, v in meta["eval"].items()}
    print(f"  [{label}] {args.clients} clients, shards "
          f"{tuple(data['tokens'].shape)}, poisoners {list(poisoners)}, "
          f"aggregation={aggregation} defense={defense}")

    t0 = time.time()
    hist = server.run(data, rounds=args.rounds, eval_set=eval_set)
    dt = time.time() - t0

    print("  round  loss    token_acc  stragglers  mean_trust")
    for i, (lo, a) in enumerate(zip(hist["loss"], hist["acc"])):
        late = int((~hist["on_time"][i] & hist["selected"][i]).sum())
        print(f"  {i:5d}  {lo:6.3f}  {a:9.3f}  {late:10d}  "
              f"{float(np.mean(hist['trust'][i])):10.1f}")
    if poisoners:
        final_trust = np.asarray(hist["trust"][-1])
        honest = np.setdiff1d(np.arange(args.clients), poisoners)
        print(f"  final trust: poisoners {final_trust[list(poisoners)].mean():.1f}"
              f" vs honest {final_trust[honest].mean():.1f}")
    print(f"  -> final loss {hist['loss'][-1]:.4f} ({dt:.1f}s)")
    return hist


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--samples", type=int, default=24,
                    help="sequences per client")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--topics", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=1,
                    help="client shards; >1 runs the mesh-sharded engine")
    ap.add_argument("--baseline", action="store_true",
                    help="run ONLY the plain-FedAvg/no-defense baseline")
    ap.add_argument("--compare", action="store_true",
                    help="run FedAR then the baseline and compare")
    args = ap.parse_args(argv)

    if args.devices > 1:
        if args.clients % args.devices:
            ap.error(f"--clients {args.clients} must divide by "
                     f"--devices {args.devices}")
        # must land before jax initializes its backends
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    results = {}
    if not args.baseline:
        print(f"== FedAR federated LM ({args.arch}) ==")
        results["fedar"] = run(
            args, aggregation="async", defense="foolsgold_sketch",
            label="fedar",
        )
    if args.baseline or args.compare:
        print("== plain FedAvg baseline (no defense) ==")
        results["baseline"] = run(
            args, aggregation="fedavg", defense="none", label="baseline",
        )
    if args.compare:
        f, b = results["fedar"], results["baseline"]
        print(f"\nFedAR final {f['loss'][-1]:.4f} vs baseline "
              f"{b['loss'][-1]:.4f} (both converge; FedAR additionally "
              f"masks stragglers and down-weights the poisoners)")
    return results


if __name__ == "__main__":
    main()
