"""Multi-trial worker: one process leases up to ``--slots`` trials from the
PR-1 TCP server and trains them all in the on-device population engine.

  PYTHONPATH=src python -m repro.population.worker --host H --port P \\
      --game pong --slots 8

This is the deployment shape where a single GPU node serves an entire
HyperTrick search: the ACQUIRE verb carries a ``slots`` hint, the server
grants a batch of leases, and the engine keeps every leased trial training
inside vmapped jitted steps while a heartbeat thread renews all the leases.
A lease the server reaps (this worker presumed dead, or a server restart)
is abandoned mid-flight — its slot is masked and hot-swapped, the same
strictly-local effect as a whole-worker death in the scalar protocol.
"""
from __future__ import annotations

import argparse
import threading
import uuid
from typing import Optional

import jax

from repro.distributed.client import ServiceClient, ServiceError
from repro.distributed.protocol import ProtocolError
from repro.launch.runtime import use_compile_cache
from repro.population.engine import PopulationEngine, RemoteDriver


class PopulationWorkerAgent:
    """``WorkerAgent`` generalized from one leased trial to a population."""

    def __init__(self, client: ServiceClient, engine: PopulationEngine,
                 heartbeat_interval: float = 2.0,
                 node: Optional[int] = None):
        self.client = client
        self.engine = engine
        # distributed tracing on by default, as in WorkerAgent: the
        # engine's phase reports stitch into per-trial server spans
        if getattr(client, "trace_ctx", None) is None:
            client.trace_ctx = (f"pop{node}-{uuid.uuid4().hex[:6]}"
                                if node is not None
                                else f"pop-{uuid.uuid4().hex[:6]}")
        self.driver = RemoteDriver(client, node=node)
        self.heartbeat_interval = heartbeat_interval
        self._stop = threading.Event()

    def run(self) -> int:
        """Drive the engine until the search budget is spent or the server
        goes away. Returns the number of phase reports delivered."""
        hb = threading.Thread(target=self._heartbeat_loop, daemon=True)
        hb.start()
        try:
            # only driver I/O means "server gone"; engine/XLA failures must
            # propagate (an OOM swallowed here would loop forever through
            # lease-reap -> requeue -> same worker -> same OOM)
            records = self.engine.run(self.driver)
        except (ServiceError, ProtocolError, OSError):
            records = self.engine.records    # server gone — we are done
        finally:
            self._stop.set()
            hb.join(timeout=2 * self.heartbeat_interval)
        return len(records)

    def _heartbeat_loop(self):
        while not self._stop.wait(self.heartbeat_interval):
            try:
                for tid in self.engine.active_trial_ids():
                    ok = self.client.heartbeat(tid)
                    if not ok:
                        self.driver.mark_lost(tid)
            except Exception:               # noqa: BLE001 — never let the
                continue                    # lease-renewal thread die


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--objective", default="ga3c", choices=("ga3c", "lm"),
                    help="engine workload (population.objectives): ga3c "
                         "trains --game, lm fine-tunes the reduced --arch "
                         "model with per-trial lr/clip/warmup on the slot "
                         "axis")
    ap.add_argument("--game", default="pong")
    ap.add_argument("--arch", default="yi-9b",
                    help="configs.registry architecture for --objective lm")
    ap.add_argument("--lm-batch", type=int, default=2)
    ap.add_argument("--lm-seq", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--n-envs", type=int, default=16)
    ap.add_argument("--episodes-per-phase", type=int, default=20,
                    help="phase length in the objective's progress units "
                         "(GA3C: finished episodes; lm: updates)")
    ap.add_argument("--max-updates", type=int, default=2000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--node", type=int, default=None)
    ap.add_argument("--heartbeat-interval", type=float, default=2.0)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the slot axis across this many local "
                         "devices (shard_map); forces the CPU device count "
                         "via XLA_FLAGS on CPU-only hosts")
    ap.add_argument("--bracket", action="store_true",
                    help="join the server-side successive-halving bracket: "
                         "acquires carry the rung-0 refill hint and rung-"
                         "phase reports park until the cohort — pooled "
                         "across every participating host — resolves. The "
                         "demotion factor eta is the SERVER's (set where "
                         "the service is built); --eta here only marks "
                         "participation")
    ap.add_argument("--eta", type=int, default=3)
    args = ap.parse_args(argv)

    if args.bracket and args.eta < 2:
        ap.error("--eta must be >= 2 (demote bottom 1/eta per rung)")
    use_compile_cache()
    mesh = None
    if args.devices > 1:
        # jax is imported but its backend is not initialized until the
        # first device lookup, so forcing the flag here still works
        from repro.launch.mesh import (force_host_device_count,
                                       make_population_mesh)
        force_host_device_count(args.devices)
        mesh = make_population_mesh(args.devices, 1)

    if args.objective == "lm":
        from repro.population.objectives.lm import LMObjective
        workload = LMObjective(arch=args.arch, batch=args.lm_batch,
                               seq=args.lm_seq, data_seed=args.seed)
    else:
        workload = args.game
    engine = PopulationEngine(workload, max_slots=args.slots,
                              n_envs=args.n_envs,
                              episodes_per_phase=args.episodes_per_phase,
                              max_updates=args.max_updates, seed=args.seed,
                              mesh=mesh,
                              bracket_eta=args.eta if args.bracket else None)
    try:
        client = ServiceClient(args.host, args.port)
    except OSError as e:
        print(f"cannot reach server at {args.host}:{args.port}: {e}")
        return 1
    with client:
        agent = PopulationWorkerAgent(
            client, engine, heartbeat_interval=args.heartbeat_interval,
            node=args.node)
        n = agent.run()
    dev = jax.devices()[0]
    print(f"population worker node={args.node} on {dev.platform} "
          f"({dev.device_kind}) delivered {n} phase reports "
          f"({engine.total_env_steps} env steps)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
