"""HyperTrick metaoptimization driver — the paper's technique as a
first-class feature over ANY registered objective, on ANY backend.

  # paper-faithful: tune GA3C on a mini-Atari game (in-process threads)
  PYTHONPATH=src python -m repro.launch.tune --objective rl --game pong \\
      --workers 12 --nodes 4 --phases 5 --eviction-rate 0.25

  # framework integration: tune LM training of a zoo architecture
  PYTHONPATH=src python -m repro.launch.tune --objective lm --arch yi-9b \\
      --workers 8 --nodes 2 --phases 4

  # on-device population engine: every live trial trains at once inside
  # one vmapped jitted step (works for --objective rl AND lm)
  PYTHONPATH=src python -m repro.launch.tune --objective lm \\
      --backend vectorized --workers 4 --phases 3

  # distributed: OS-process workers against a fault-tolerant TCP server
  # with a durable journal (resume with --resume after a server death)
  PYTHONPATH=src python -m repro.launch.tune --backend server \\
      --objective synthetic --workers 8 --nodes 2 --phases 3 \\
      --journal /tmp/metaopt_journal.jsonl
"""
from __future__ import annotations

import argparse
import json

from repro.core.executor import (PopulationCluster, ProcessCluster,
                                 ThreadCluster)
from repro.core.hypertrick import HyperTrick, RandomSearchPolicy
from repro.core.completion import expected_alpha, min_alpha
from repro.core.search_space import (LogUniform, SearchSpace, lm_space,
                                     paper_rl_space)
from repro.launch.runtime import use_compile_cache


def synthetic_space() -> SearchSpace:
    """Planted-optimum toy space for demos / backend smoke runs."""
    return SearchSpace({"x": LogUniform(0.01, 100.0)})


def build_objective_spec(args) -> dict:
    """JSON-able spec resolved by repro.distributed.worker in each process."""
    from repro.distributed.worker import build_spec
    return build_spec(args.objective, game=args.game, arch=args.arch,
                      episodes_per_phase=args.episodes_per_phase,
                      steps_per_phase=args.steps_per_phase,
                      seed=args.seed, synthetic_sleep=args.synthetic_sleep)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--objective", choices=["rl", "lm", "synthetic"],
                    default="rl")
    ap.add_argument("--game", default="pong")
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--workers", type=int, default=12)     # W0
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--phases", type=int, default=5)       # N_p
    ap.add_argument("--eviction-rate", type=float, default=0.25)
    ap.add_argument("--episodes-per-phase", type=int, default=60)
    ap.add_argument("--steps-per-phase", type=int, default=25)
    ap.add_argument("--synthetic-sleep", type=float, default=0.05)
    ap.add_argument("--policy", choices=["hypertrick", "random"],
                    default="hypertrick")
    ap.add_argument("--scheduler",
                    choices=["hypertrick", "random", "hyperband", "pbt"],
                    default=None,
                    help="trial-lifecycle scheduler (core.scheduler): "
                         "hypertrick/random keep the classic async "
                         "policies (same results as --policy); hyperband "
                         "runs EVERY bracket of the (eta, R=--phases) "
                         "construction concurrently through the service's "
                         "rung barrier, cohorts keyed by (bracket_id, "
                         "rung) — backends process/server; pbt runs a "
                         "population of --workers trials with exploit/"
                         "explore CLONE verdicts — on --backend vectorized "
                         "the clone is a device-side slot-to-slot copy of "
                         "the parent's weights")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend",
                    choices=["thread", "process", "server", "vectorized"],
                    default="thread",
                    help="thread: in-process node threads; process: OS-"
                         "process workers over TCP; server: process workers "
                         "plus a durable journal (resumable); vectorized: "
                         "the on-device population engine — all live trials "
                         "train simultaneously in vmapped jitted steps "
                         "(rl and lm objectives)")
    ap.add_argument("--slots", type=int, default=None,
                    help="vectorized: simultaneous on-device trials "
                         "(default: --workers); process/server with an rl "
                         "or lm objective: trials leased per worker process "
                         "(default 1 = classic scalar workers)")
    ap.add_argument("--devices", type=int, default=1,
                    help="vectorized: shard the slot axis across this many "
                         "devices (shard_map over a slots x data mesh). On "
                         "a CPU-only host the device count is forced via "
                         "XLA_FLAGS=--xla_force_host_platform_device_count "
                         "automatically")
    ap.add_argument("--bracket", action="store_true",
                    help="successive-halving rungs via the service-side "
                         "generation barrier: rung phases (eta^k - 1) park "
                         "reports until the cohort is complete, then the "
                         "bottom 1/eta is demoted. On --backend vectorized "
                         "the cohort is the local population; on process/"
                         "server ONE bracket spans every worker process "
                         "(cohorts pool across hosts). The service policy "
                         "becomes a pure sampler (--policy is ignored)")
    ap.add_argument("--eta", type=int, default=3,
                    help="rung demotion factor for --bracket (default 3)")
    ap.add_argument("--n-envs", type=int, default=16,
                    help="vectorized envs per trial (vectorized backend)")
    ap.add_argument("--journal", default=None,
                    help="journal path (default for --backend server: "
                         "metaopt_journal.jsonl; optional for process). "
                         "A fresh run overwrites an existing journal; use "
                         "--resume to replay it instead")
    ap.add_argument("--resume", action="store_true",
                    help="replay an existing journal before serving")
    ap.add_argument("--lease-ttl", type=float, default=15.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    use_compile_cache()

    if args.objective == "rl":
        space = paper_rl_space()
    elif args.objective == "lm":
        space = lm_space()
    else:
        space = synthetic_space()

    scheduler = args.scheduler or args.policy
    if scheduler == "hyperband":
        if args.bracket:
            ap.error("--scheduler hyperband IS a bracket scheduler (every "
                     "(eta, R) bracket runs concurrently); drop --bracket")
        if args.backend not in ("process", "server"):
            ap.error("--scheduler hyperband pools its bracket cohorts at "
                     "the server-side rung barrier; use --backend process "
                     "or server")
        from repro.core.scheduler import HyperbandScheduler
        policy = HyperbandScheduler(space, n_phases=args.phases,
                                    eta=args.eta, seed=args.seed)
    elif scheduler == "pbt":
        if args.bracket:
            ap.error("--scheduler pbt is asynchronous (no rung barrier); "
                     "drop --bracket")
        from repro.core.scheduler import PBTScheduler
        from repro.population.objectives import spec_for
        # perturb rules come from the OBJECTIVE: its structural keys (rl:
        # t_max, lm: loss_chunk) stay frozen under CLONE perturbation —
        # a perturbed structural value would silently re-bucket (rl) or
        # recompile (lm) the child
        policy = PBTScheduler(space, population=args.workers,
                              n_phases=args.phases, seed=args.seed,
                              frozen=spec_for(args.objective).structural)
    elif args.bracket:
        # rung demotion needs a pure sampler upstream: the W0
        # configurations come from the service, every eviction decision is
        # the barrier's ranking
        policy = RandomSearchPolicy(space, args.workers, args.phases,
                                    seed=args.seed)
    elif scheduler == "hypertrick":
        policy = HyperTrick(space, args.workers, args.phases,
                            args.eviction_rate, seed=args.seed)
    else:
        policy = RandomSearchPolicy(space, args.workers, args.phases,
                                    seed=args.seed)

    if args.backend != "vectorized" and args.devices > 1:
        ap.error("--devices drives the on-device population engine; use "
                 "--backend vectorized")
    if args.backend == "thread" and args.bracket:
        ap.error("--bracket needs the service-side rung barrier; use "
                 "--backend vectorized (one host) or process/server "
                 "(multi-host brackets)")
    if (args.bracket or scheduler == "hyperband") and args.eta < 2:
        ap.error("--eta must be >= 2 (demote bottom 1/eta per rung)")

    if args.backend == "vectorized":
        if args.objective not in ("rl", "lm"):
            ap.error("--backend vectorized runs the on-device population "
                     "engine; use --objective rl or lm")
        if args.resume or args.journal:
            ap.error("--journal/--resume need a socket backend "
                     "(--backend process or server)")
        if args.devices > 1:
            # must land before jax initializes its backend (nothing above
            # touches jax); a no-op on hosts that already have the devices
            from repro.launch.mesh import force_host_device_count
            force_host_device_count(args.devices)
        if args.objective == "lm":
            pop_objective = {"kind": "lm", "arch": args.arch,
                             "data_seed": args.seed}
            units_per_phase = args.steps_per_phase
        else:
            pop_objective = None          # default: GA3C on --game
            units_per_phase = args.episodes_per_phase
        cluster = PopulationCluster(
            args.slots or args.workers, game=args.game,
            objective=pop_objective,
            episodes_per_phase=units_per_phase,
            n_envs=args.n_envs, seed=args.seed, devices=args.devices,
            bracket_eta=args.eta if args.bracket else None)
    elif args.backend == "thread":
        if args.resume or args.journal:
            ap.error("--journal/--resume need a socket backend "
                     "(--backend process or server)")
        if args.objective == "rl":
            from repro.rl.ga3c import make_rl_objective
            objective = make_rl_objective(args.game, args.episodes_per_phase,
                                          seed=args.seed)
        elif args.objective == "lm":
            from repro.train.trainer import make_lm_objective
            objective = make_lm_objective(args.arch, args.steps_per_phase,
                                          seed=args.seed)
        else:
            from repro.distributed.worker import make_synthetic_objective
            objective = make_synthetic_objective(sleep=args.synthetic_sleep,
                                                 seed=args.seed)
        cluster = ThreadCluster(args.nodes, objective)
    else:
        journal_path = args.journal
        if args.backend == "server" and journal_path is None:
            journal_path = "metaopt_journal.jsonl"
        if args.resume and journal_path is None:
            ap.error("--resume requires a journal "
                     "(--backend server or --journal PATH)")
        if args.slots and args.slots > 1 and args.objective not in ("rl",
                                                                    "lm"):
            ap.error("--slots > 1 (population workers) requires "
                     "--objective rl or lm")
        cluster = ProcessCluster(args.nodes, build_objective_spec(args),
                                 lease_ttl=args.lease_ttl,
                                 journal_path=journal_path,
                                 resume=args.resume,
                                 slots=args.slots or 1,
                                 bracket_eta=(args.eta if args.bracket
                                              else None))

    result = cluster.run(policy)
    summary = result.summary()
    summary["expected_alpha"] = expected_alpha(args.eviction_rate, args.phases)
    summary["min_alpha"] = min_alpha(args.eviction_rate, args.phases)
    print(json.dumps(summary, indent=2, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2, default=str)
    return result


if __name__ == "__main__":
    main()
