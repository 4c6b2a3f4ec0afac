"""End-to-end federated LM training driver (CPU-scale; TPU-shaped).

Runs the full production stack on whatever devices exist: config-driven
model, sharded train step, federated pod-axis rounds (FedAvg with optional
int8 round compression), BS-timed rounds via the PON co-simulation,
checkpoint/restart. This is the driver the examples call; on a real fleet
only the mesh constructor changes.

Usage:
  python -m repro.launch.train --arch olmo-1b --smoke --steps 50 \
      --rounds 5 --ckpt-dir /tmp/ckpt

Observability: ``--log-jsonl PATH`` writes every console line as a
structured JSON event (the console stays a formatted view of the same
events) plus per-round records and a final metrics summary;
``--trace PATH`` writes a Chrome-trace JSON of the run's spans
(open in Perfetto / chrome://tracing).
"""
from __future__ import annotations

import argparse
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.slicing import ClientProfile
from repro.data import TokenBatcher, lm_tokens
from repro.dist import stepfns
from repro.faults import FaultSchedule
from repro.launch.mesh import make_host_mesh
from repro.launch.specs import state_shapes, state_spec_tree
from repro.net.api import SweepSpec, simulate
from repro.net.engine import SweepCase
from repro.net.jobs import JobSpec, make_competing_jobs
from repro.net.multi_pon import MultiPonTopology
from repro.net.sim import FLRoundWorkload, PONConfig
from repro.net.timeline import TimelineSchedule
from repro.optim.optimizers import OptimizerConfig
from repro.optim.schedules import warmup_cosine


class FedPods:
    """The federated programs on a ``("pod", "data", "model")`` mesh.

    Every leaf of the pod-stacked state, and every batch, is split over
    the ``pod`` mesh axis, so each pod's replica and optimizer state
    live on its own devices; the jitted programs keep that layout
    (``spmd_axis_name="pod"`` inside the vmapped local step, pod-sharded
    outputs from the round steps).
    """

    def __init__(self, cfg, opt_cfg, mesh, n_pods: int, schedule=None):
        self.cfg, self.opt_cfg, self.n_pods = cfg, opt_cfg, n_pods
        specs = state_spec_tree(state_shapes(cfg, opt_cfg, n_pods), cfg,
                                mesh, fed=True)
        self.state_sharding = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        self.batch_sharding = NamedSharding(mesh, P("pod"))
        self.step = jax.jit(
            stepfns.make_fed_train_step(cfg, opt_cfg, schedule,
                                        spmd_axis_name="pod"),
            in_shardings=(self.state_sharding, self.batch_sharding),
            out_shardings=(self.state_sharding, None))

    def init_state(self, key) -> stepfns.TrainState:
        return jax.jit(partial(stepfns.init_fed_state, cfg=self.cfg,
                               opt_cfg=self.opt_cfg, n_pods=self.n_pods),
                       out_shardings=self.state_sharding)(key)

    def batch(self, parts) -> dict:
        """Stack one host batch per pod onto the pod axis."""
        return {k: jax.device_put(np.stack([np.asarray(p[k]) for p in parts]),
                                  self.batch_sharding)
                for k in parts[0]}

    def round_step(self, compress: Optional[str] = None):
        return jax.jit(stepfns.make_fed_round_step(self.cfg,
                                                   compress=compress),
                       out_shardings=self.state_sharding)

    def _async_sharding(self):
        p = self.state_sharding.params
        return stepfns.AsyncRoundState(global_params=p, refs=p, pending=p)

    def init_async_state(self, state) -> stepfns.AsyncRoundState:
        return jax.jit(stepfns.init_async_state,
                       out_shardings=self._async_sharding())(state)

    def async_round_step(self, **kw):
        return jax.jit(stepfns.make_async_round_step(self.cfg, **kw),
                       out_shardings=(self.state_sharding,
                                      self._async_sharding()))


def train(
    arch: str = "olmo-1b",
    smoke: bool = True,
    steps_per_round: int = 20,
    rounds: int = 3,
    n_pods: int = 2,
    global_batch: int = 8,
    seq_len: int = 64,
    lr: float = 3e-3,
    ckpt_dir: Optional[str] = None,
    policy: str = "bs",
    load: float = 0.8,
    compress: str = "int8",
    log_every: int = 10,
    config_overrides: Optional[dict] = None,
    n_pons: int = 1,
    cps_gbps: Optional[float] = None,
    deadline_s: Optional[float] = None,
    deadline_policy: str = "defer",
    async_buffer: Optional[int] = None,
    log_jsonl: Optional[str] = None,
    trace_path: Optional[str] = None,
    collector=None,
    resume: bool = True,
    dropout_rate: float = 0.0,
    outage_rate: float = 0.0,
    loss_rate: float = 0.0,
    fault_seed: int = 0,
    quorum: Optional[float] = None,
    jobs: int = 0,
    fairness: str = "maxmin",
):
    from repro.obs import Collector, EventLog, SpanTracer
    from repro.obs.trace import maybe_span

    if jobs > 0 and (deadline_s is not None or async_buffer is not None
                     or quorum is not None or dropout_rate > 0.0
                     or outage_rate > 0.0 or loss_rate > 0.0):
        raise ValueError(
            "--jobs contention runs plain rounds: deadlines, async "
            "buffering, fault injection and quorum are single-tenant "
            "features (per-job deadlines go through JobSpec.deadline_s)"
        )

    cfg = get_config(arch, smoke=smoke).replace(grad_accum=1)
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    opt_cfg = OptimizerConfig(name="adamw", lr=lr)
    schedule = warmup_cosine(lr, 20, steps_per_round * rounds)

    log = EventLog(jsonl_path=log_jsonl)
    if collector is None and (log_jsonl or trace_path):
        collector = Collector(
            tracer=SpanTracer(enabled=trace_path is not None)
        )

    n_dev = jax.device_count()
    pods = n_pods if n_dev % n_pods == 0 and n_dev >= n_pods else 1
    mesh = make_host_mesh(model_parallel=1, pods=pods) if pods > 1 else (
        make_host_mesh(model_parallel=1)
    )
    log.emit("mesh", echo="mesh: {shape} devices={devices}",
             shape=dict(mesh.shape), devices=n_dev, arch=arch,
             pods=pods, policy=policy, load=load)

    # federated data: one disjoint shard per pod
    tokens = lm_tokens(400_000, cfg.vocab_size, seed=0)
    batchers = [
        TokenBatcher(tokens, global_batch // max(pods, 1), seq_len,
                     seed=i, pod_index=i, n_pods=max(pods, 1))
        for i in range(max(pods, 1))
    ]
    iters = [iter(b) for b in batchers]

    with mesh:
        fed = pods > 1
        if fed:
            fed_pods = FedPods(cfg, opt_cfg, mesh, pods, schedule)
            state = fed_pods.init_state(jax.random.PRNGKey(0))
            step = fed_pods.step
            round_step = fed_pods.round_step(compress)
        else:
            state = stepfns.init_train_state(
                jax.random.PRNGKey(0), cfg, opt_cfg
            )
            step = jax.jit(stepfns.make_train_step(cfg, opt_cfg, schedule))
            round_step = None

        # deadline/async rounds: not every pod's update reaches every
        # aggregation — the buffered staleness-weighted round step is
        # driven from the simulated arrivals instead of the plain
        # FedAvg. Built BEFORE restore so the checkpoint template
        # matches what gets saved (train + async state as one tree).
        coupled = fed and (deadline_s is not None or async_buffer is not None)
        if coupled:
            astate = fed_pods.init_async_state(state)
            around = fed_pods.async_round_step(
                compress=compress, quorum_frac=quorum,
                quorum_expected=pods if quorum is not None else None,
            )

        mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
        start_round = 0
        if mgr is not None and resume:
            template = {"train": state, "async": astate} if coupled else state
            # restore onto the fresh state's placement (pod-sharded)
            restored = mgr.restore_latest(
                like=template,
                shardings=jax.tree.map(lambda x: x.sharding, template))
            if restored is not None:
                tree, meta = restored
                if coupled:
                    state, astate = tree["train"], tree["async"]
                else:
                    state = tree
                start_round = int(meta.get("round", 0))
                log.emit("resume", echo="resumed from round {round}",
                         round=start_round)
                # fast-forward the deterministic data streams to where
                # the checkpointed run stopped — a resumed run must
                # consume the same batch sequence as an uninterrupted
                # one (TokenBatcher is a pure function of its seed)
                for _ in range(start_round * steps_per_round):
                    for g in iters:
                        next(g)

        # PON timing for the round (the paper's co-simulation); the slice
        # is sized for the measured payloads, not the paper's CNN
        # constant: compressed per-pod uplink, fp32 broadcast downlink
        up_bits = float(stepfns.fed_update_bits(cfg, compress))
        down_bits = float(stepfns.fed_update_bits(cfg, "none"))
        log.emit("payload", compress=compress, upload_bits=up_bits,
                 model_bits=down_bits)
        rng = np.random.default_rng(0)
        profiles = [
            ClientProfile(client_id=i, t_ud=float(t), t_dl=0.0,
                          m_ud_bits=up_bits)
            for i, t in enumerate(rng.uniform(1.0, 5.0, max(pods, 2)))
        ]
        # several OLT/wavelength segments sharing a CPS uplink: the PON
        # config describes ONE segment. Client i sits on global ONU
        # i % (n_pons * n_onus) with PON = onu // n_onus, so spreading
        # the pods over the stack needs n_onus = ceil(pods / n_pons)
        # exactly (any larger floor would cluster them on PON 0).
        # competitor jobs (--jobs) add 2 clients each above the pods,
        # so the ONU stack must cover the whole tenant population
        n_clients = max(pods, 2) + 2 * max(jobs, 0)
        if n_pons > 1:
            pon = PONConfig(n_onus=max(1, -(-n_clients // n_pons)))
        else:
            pon = PONConfig(n_onus=max(8, n_clients))
        topology = None
        if n_pons > 1 or cps_gbps is not None:
            topology = MultiPonTopology(
                n_pons=n_pons,
                cps_rate_bps=None if cps_gbps is None else cps_gbps * 1e9,
            )
        # one stacked multi-round timeline provides every round's sync
        # time (per-round arrival streams, not one number reused R times);
        # deadlines/async cut rounds short and hand arrivals + staleness
        # to the aggregation step below. ALWAYS the full schedule, even
        # on resume: round r's counter streams are keyed by r, so a
        # resumed run replays the identical network realization and
        # lands on the same final params as an uninterrupted one.
        faults = None
        if dropout_rate > 0.0 or outage_rate > 0.0 or loss_rate > 0.0:
            faults = FaultSchedule(
                seed=fault_seed, dropout_rate=dropout_rate,
                outage_rate=outage_rate, loss_rate=loss_rate,
            )
        job_specs = None
        if jobs > 0:
            # the pods' FL task becomes tenant job 0; --jobs competitor
            # jobs (half-size models, 2 clients each) contend with it
            # under --fairness inside the same PON/CPS cycle
            comp, extra = make_competing_jobs(
                [p.client_id for p in profiles], down_bits, jobs
            )
            job_specs = (JobSpec(
                job_id=0,
                clients=tuple(p.client_id for p in profiles),
                model_bits=down_bits,
            ),) + comp
            profiles = profiles + list(extra)
            log.emit("jobs", echo="tenant jobs: {n} competitors "
                     "(fairness={fairness})", n=jobs, fairness=fairness)
        wl = FLRoundWorkload(clients=profiles, model_bits=down_bits)
        n_net_rounds = max(rounds, 1)
        net_spec = SweepSpec(
            cases=(SweepCase(workload=wl, load=load, policy=policy,
                             seed=0, topology=topology, jobs=job_specs,
                             fairness=fairness),),
            pon=pon,
            schedule=TimelineSchedule(n_rounds=n_net_rounds,
                                      deadline_s=deadline_s,
                                      deadline_policy=deadline_policy,
                                      buffer_k=async_buffer,
                                      faults=faults,
                                      quorum_frac=quorum),
        )
        with maybe_span(collector, "net:timeline", rounds=n_net_rounds):
            timeline = simulate(net_spec, collector=collector)[0]
        if job_specs is not None:
            # the pods' wall clock follows THEIR job's sync time; the
            # competitors only show up as contention
            sync_times = np.array([
                rnd.job_sync.get(0, rnd.sync_time)
                for rnd in timeline.rounds
            ])
        else:
            sync_times = timeline.sync_times

        wall_simulated = 0.0
        # pods whose failed upload is retrying (they re-enter the
        # timeline as carriers and must NOT re-snapshot their payload);
        # replayed over the pre-resume rounds so a resumed run holds
        # the same fault bookkeeping as an uninterrupted one
        in_retry: set = set()
        for rn in timeline.rounds[:start_round]:
            in_retry |= set(rn.failed) | set(rn.lost)
            in_retry -= set(rn.arrived) | set(rn.gave_up)
        history = []
        for rnd in range(start_round, rounds):
            t0 = time.time()
            losses = []
            for it in range(steps_per_round):
                if fed:
                    batch = fed_pods.batch([next(g) for g in iters])
                else:
                    batch = {
                        k: jnp.asarray(v) for k, v in next(iters[0]).items()
                    }
                state, metrics = step(state, batch)
                loss = float(jnp.mean(metrics["loss"]))
                losses.append(loss)
                if it % log_every == 0:
                    log.emit(
                        "step",
                        echo="round {round} step {step}: loss={loss:.4f}",
                        round=rnd, step=it, loss=loss,
                    )
            if fed:
                weights = jnp.ones((pods,), jnp.float32)
                if coupled:
                    idx = min(rnd, len(timeline.rounds) - 1)
                    rn = timeline.rounds[idx]
                    prev_def = (timeline.rounds[idx - 1].deferred
                                if idx > 0 else {})
                    # a retry join is in ul_bits but not a fresh entry:
                    # it re-sends its snapshotted payload unchanged
                    fresh = set(rn.ul_bits) - set(prev_def) - in_retry
                    contrib = {cid: 1.0 for cid in rn.arrived}
                    contrib.update({cid: f for cid, f in rn.partial.items()
                                    if f > 0.0})
                    arrived = np.zeros(pods, bool)
                    stale = np.zeros(pods, np.int32)
                    fracs = np.ones(pods, np.float32)
                    snap = np.zeros(pods, bool)
                    rejoin = np.zeros(pods, bool)
                    for cid in range(pods):
                        snap[cid] = cid in fresh
                        if cid in contrib:
                            arrived[cid] = True
                            fracs[cid] = contrib[cid]
                            stale[cid] = rn.staleness.get(cid, 0)
                        # every cut pod re-enters fresh — including a
                        # partial pod whose served fraction was 0 (its
                        # update is discarded exactly like a drop) and
                        # a pod that gave up on its retries
                        if (cid in contrib or cid in rn.dropped
                                or cid in rn.partial
                                or cid in rn.gave_up):
                            rejoin[cid] = True
                    state, astate = around(
                        state, astate, weights, jnp.asarray(arrived),
                        jnp.asarray(stale), jnp.asarray(fracs),
                        jnp.asarray(snap), jnp.asarray(rejoin),
                    )
                    in_retry |= set(rn.failed) | set(rn.lost)
                    in_retry -= set(rn.arrived) | set(rn.gave_up)
                else:
                    state = round_step(state, weights)
            sync = float(sync_times[min(rnd, len(sync_times) - 1)])
            wall_simulated += sync
            entry = {"round": rnd, "loss": float(np.mean(losses)),
                     "sync_s": sync, "wall_s": time.time() - t0}
            history.append(entry)
            log.emit("round", **entry)
            if mgr is not None:
                tree = {"train": state, "async": astate} if coupled else state
                mgr.save(rnd + 1, tree, metadata={"round": rnd + 1})
        if mgr is not None:
            mgr.wait()
        if history:
            log.emit(
                "done",
                echo="done: {rounds} rounds, final loss {loss:.4f}, "
                     "simulated FL wall-clock {wall_s:.1f}s "
                     "({policy} @ load {load})",
                rounds=rounds, loss=history[-1]["loss"],
                wall_s=wall_simulated, policy=policy, load=load,
            )
        else:
            log.emit(
                "done",
                echo="nothing to do: resumed at round {round}/{rounds}",
                round=start_round, rounds=rounds, loss=None,
                wall_s=0.0, policy=policy, load=load,
            )
        if collector is not None:
            log.emit("metrics", summary=collector.report().to_dict())
            if trace_path:
                collector.tracer.save(trace_path)
        log.close()
        return state, history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--policy", choices=("bs", "fcfs"), default="bs")
    ap.add_argument("--load", type=float, default=0.8)
    ap.add_argument("--pons", type=int, default=1,
                    help="wavelength/OLT segments sharing the CPS uplink")
    ap.add_argument("--cps-gbps", type=float, default=None,
                    help="CPS uplink rate in Gb/s (default uncontended)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="round upload deadline in seconds (stragglers "
                         "handled per --deadline-policy)")
    ap.add_argument("--deadline-policy", default="defer",
                    choices=("defer", "drop", "partial"),
                    help="what happens to a straggler's unserved bits "
                         "at the deadline")
    ap.add_argument("--async-buffer", type=int, default=None,
                    help="async (FedBuff) mode: aggregate as soon as K "
                         "uploads complete; stragglers defer with "
                         "staleness")
    ap.add_argument("--log-jsonl", default=None,
                    help="write structured JSONL events to this path "
                         "(console lines become a formatted view of "
                         "the same events)")
    ap.add_argument("--trace", default=None,
                    help="write a Chrome-trace JSON of the run's spans "
                         "to this path (view in Perfetto)")
    ap.add_argument("--resume", dest="resume", action="store_true",
                    default=True,
                    help="resume from the latest checkpoint in "
                         "--ckpt-dir (the default); a resumed run "
                         "reproduces an uninterrupted run exactly")
    ap.add_argument("--no-resume", dest="resume", action="store_false",
                    help="ignore existing checkpoints and start fresh")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-round client dropout probability "
                         "(deterministic counter-based fault stream)")
    ap.add_argument("--outage-rate", type=float, default=0.0,
                    help="per-round probability of an upstream "
                         "link-outage window per PON")
    ap.add_argument("--loss-rate", type=float, default=0.0,
                    help="per-round probability a completed upload's "
                         "payload arrives corrupted")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault-injection streams")
    ap.add_argument("--quorum", type=float, default=None,
                    help="quorum aggregation: a round commits only "
                         "when at least this fraction of pending "
                         "uploads arrived (needs --deadline)")
    ap.add_argument("--jobs", type=int, default=0,
                    help="competitor FL jobs contending with the pods' "
                         "task inside the same PON/CPS cycle (each "
                         "brings 2 clients and a half-size model)")
    ap.add_argument("--fairness", default="maxmin",
                    choices=("maxmin", "weighted", "deadline"),
                    help="how each cycle's capacity is split across "
                         "tenant jobs")
    args = ap.parse_args(argv)
    train(
        arch=args.arch, smoke=args.smoke, steps_per_round=args.steps,
        rounds=args.rounds, n_pods=args.pods, global_batch=args.batch,
        seq_len=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
        policy=args.policy, load=args.load,
        n_pons=args.pons, cps_gbps=args.cps_gbps,
        deadline_s=args.deadline, deadline_policy=args.deadline_policy,
        async_buffer=args.async_buffer,
        log_jsonl=args.log_jsonl, trace_path=args.trace,
        resume=args.resume,
        dropout_rate=args.dropout_rate, outage_rate=args.outage_rate,
        loss_rate=args.loss_rate, fault_seed=args.fault_seed,
        quorum=args.quorum,
        jobs=args.jobs, fairness=args.fairness,
    )


if __name__ == "__main__":
    main()
