"""Federated training cells: int8 FedAvg over pods, one pod per chip.

The timed path is the trainer's own (``repro.launch.train.FedPods``),
driven as ``train(n_pods=4, compress="int8")`` drives it: a call is one
FL round, a local AdamW step of every pod per batch (``local_step``,
the pods' losses read back after each as the trainer reads them), then
one int8 FedAvg round over the pod axis (``round_step``), waited for.
The deployment (``configs/<name>.json``) gives the model, the pods and
the optimizer; the traffic mix (``traffic/<name>.json``) the local
steps per round, the per-pod batch and the sequence length.  Weights
come from the run's seed, and tokens from ``repro.data.lm_tokens`` of
the seed, one disjoint shard per pod; which sequences of its shard a
pod trains on in each step of a call is drawn from the seed and the
call's index.

The check draws, from the seed, a window call, one of its local steps
and a pod.  The timed calls keep nothing for it, so the device holds
what a deployment holds: once the window has closed, the check replays
the run from the seed up to that call with the same programs, and
counts a mismatch unless the replay's losses of the call are bitwise
the window's.  It keeps on the host the pod's state before the drawn
step and its parameters after it, and compares with the plain
reference (:mod:`bench.reference_fed`), computed in blocks of sequences
on the pod's batch: the step's loss; the step's change of every
parameter, the reference's gradients through the reference's AdamW at
the program's step count and scheduled rate (``step_rel_gap``, the
worst leaf's ``|d_prog - d_ref| / |d_ref|``: a step that changed
nothing reads 1); and the round's averaged parameters, each pod's copy,
against the reference's int8 FedAvg of the pre-round parameters,
relative to the change the round made.
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference_fed as ref

RATE = "sim_rounds_per_s"       # FL rounds (local steps + FedAvg) per second
MASK64 = (1 << 64) - 1
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops
PROGRAMS = ("jit_fed_step", "jit_fed_round")

# the cell whose programs the per-layer readers of this kind read (see
# ``scope_seconds``); the harness builds one cell per process
CURRENT: list = []


def model_config(config: dict):
    """The ``repro`` ``ModelConfig`` of a deployment's ``model`` block."""
    from repro.configs import get_config
    from repro.configs.base import SSMConfig

    m = config["model"]
    return get_config(config["arch"]).replace(
        n_layers=m["n_layer"], d_model=m["d_model"],
        vocab_size=m["vocab_size"], tie_embeddings=m["tie_embeddings"],
        ssm=SSMConfig(d_state=m["d_state"], expand=m["expand"],
                      d_head=m["headdim"], d_conv=m["d_conv"],
                      chunk=m["chunk_size"]),
        dtype=config["dtype"], param_dtype=config["param_dtype"],
        grad_accum=1)


def flops_per_round(config: dict, traffic: dict) -> float:
    """Model operations of one FL round: every pod's local steps
    (:func:`bench.reference_fed.train_flops_per_token`)."""
    m = config["model"]
    per_token = ref.train_flops_per_token(
        m["d_model"], m["n_layer"], m["vocab_size"], m["d_state"],
        m["expand"], m["headdim"], m["d_conv"], m["chunk_size"],
        traffic["seq_len"])
    tokens = (config["pods"] * traffic["local_steps"]
              * traffic["per_pod_batch"] * traffic["seq_len"])
    return per_token * tokens


def pod_tree(tree, q: int):
    """Pod ``q``'s slice of a pod-stacked tree, on the device that holds
    it; ``tree`` may also be a list of per-pod trees."""
    if isinstance(tree, list):
        return tree[q]
    return jax.tree.map(lambda a: _pod_slice(a, q, lambda d: d), tree)


def host_pod(tree, q: int):
    """Pod ``q``'s slice of a pod-stacked tree, copied to the host
    without a copy on the device."""
    return jax.tree.map(lambda a: _pod_slice(a, q, np.asarray), tree)


def _pod_slice(a, q: int, fetch):
    """Pod ``q``'s slice of the pod-stacked array ``a``, taken from
    ``fetch`` of the shard that holds it."""
    for shard in a.addressable_shards:
        idx = shard.index[0]
        start = idx.start or 0
        stop = a.shape[0] if idx.stop is None else idx.stop
        if start <= q < stop:
            return fetch(shard.data)[q - start]
    raise ValueError(f"pod {q} of a {a.shape} leaf is not addressable")


class Cell:
    """One federated cell: ``config`` and ``traffic`` are the parsed JSON
    files of its deployment and its traffic mix."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.launch.train import FedPods

        if not hasattr(FedPods, "counters"):
            raise SystemExit("this program's FedPods has no counters(): "
                             "it cannot run a fed cell")
        self.config, self.traffic = config, traffic
        self.seed = int(seed) & MASK64
        self.pods = config["pods"]
        self.steps = traffic["local_steps"]
        self.batch_size = traffic["per_pod_batch"]
        self.seq_len = traffic["seq_len"]
        self.n_cases = self.steps * self.pods   # a local step of a pod
        self.n_clients = self.pods              # the FL clients are pods
        self.shards = None
        opt = config["optimizer"]
        self.hp = {k: float(opt[k]) for k in
                   ("b1", "b2", "eps", "weight_decay", "grad_clip")}
        self.key = int(np.random.default_rng(self.seed).integers(2**31))
        rng = np.random.default_rng([self.seed, 1 << 20])
        candidates = [dict(index=1 + i) for i in range(traffic["check_calls"])]
        self.pick = self.sample(candidates, rng)[0]
        self.losses = {}          # call index -> (steps, pods) host losses
        CURRENT[:] = [self]

    # -- generator -------------------------------------------------------

    def call(self, index: int) -> dict:
        """Plain inputs of call ``index`` (0 is the warm-up): for each
        local step of each pod, the rows of the pod's shard it trains on,
        drawn without replacement from ``(seed, index)``."""
        rng = np.random.default_rng([self.seed, index])
        n_rows = self._n_rows()
        cases = [dict(step=j, pod=q, rows=sorted(
                     int(r) for r in rng.choice(n_rows, self.batch_size,
                                                replace=False)))
                 for j in range(self.steps) for q in range(self.pods)]
        return dict(index=index, cases=cases, clients=list(range(self.pods)))

    def _n_rows(self) -> int:
        """Sequences of ``seq_len + 1`` tokens in one pod's shard."""
        shard = self.traffic["token_pool"] // self.pods
        return shard // (self.seq_len + 1)

    def _shards(self):
        """Each pod's disjoint shard of the seed's token stream
        (``repro.data.lm_tokens``), as rows of ``seq_len + 1`` tokens."""
        if self.shards is None:
            from repro.data import lm_tokens

            tokens = lm_tokens(self.traffic["token_pool"],
                               self.config["model"]["vocab_size"],
                               seed=self.seed)
            n, width = self._n_rows(), self.seq_len + 1
            shard = self.traffic["token_pool"] // self.pods
            self.shards = [tokens[q * shard:q * shard + n * width]
                           .reshape(n, width) for q in range(self.pods)]
        return self.shards

    def inputs(self, index: int):
        """Window call ``index``: its plain inputs and, per local step,
        one host batch ``{"tokens", "labels"}`` per pod."""
        call = self.call(index)
        shards = self._shards()
        parts = [[None] * self.pods for _ in range(self.steps)]
        for case in call["cases"]:
            rows = shards[case["pod"]][case["rows"]]
            parts[case["step"]][case["pod"]] = {"tokens": rows[:, :-1],
                                                "labels": rows[:, 1:]}
        return call["index"], parts

    def sample(self, calls, rng):
        """The local step to check: ``(call, step, pod)`` with the call
        drawn from ``calls`` and the step and pod from ``rng``."""
        call = calls[int(rng.integers(len(calls)))]
        return [dict(call=call["index"], step=int(rng.integers(self.steps)),
                     pod=int(rng.integers(self.pods)))]

    def lr(self, count: int) -> float:
        """The scheduled learning rate of a pod's step after ``count``
        steps (:func:`bench.reference_fed.warmup_cosine`)."""
        opt = self.config["optimizer"]
        return ref.warmup_cosine(count, opt["lr"], opt["warmup_steps"],
                                 opt["total_steps"])

    # -- the run ---------------------------------------------------------

    def warmup(self) -> None:
        """Build the programs and the state, then one call of the cell's
        own shapes."""
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import FedPods
        from repro.optim.optimizers import OptimizerConfig
        from repro.optim.schedules import warmup_cosine

        opt = dict(self.config["optimizer"])
        warm, total = opt.pop("warmup_steps"), opt.pop("total_steps")
        self.cfg = model_config(self.config)
        self.mesh = make_host_mesh(model_parallel=1, pods=self.pods)
        self.fed = FedPods(self.cfg, OptimizerConfig(**opt), self.mesh,
                           self.pods, warmup_cosine(opt["lr"], warm, total))
        self.round = self.fed.round_step(self.traffic["compress"])
        self.weights = jnp.asarray(self.traffic["pod_weights"], jnp.float32)
        self.state = self.init_state()
        self.state_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), self.state)
        self.run(self.inputs(0))

    def init_state(self):
        with self.mesh:
            return self.fed.init_state(jax.random.PRNGKey(self.key))

    def run(self, inputs):
        """The timed call: one FL round; returns the pods' losses per
        step."""
        self.state, losses = self.fl_round(self.state, inputs[1])
        return losses

    def fl_round(self, state, parts, keep=None):
        """``(state, losses)`` after one FL round from ``state``; with
        ``keep`` (a dict), also put there what the check needs of the
        drawn step and pod: its state before the step and its parameters
        after it, on the host, and every pod's parameters before and
        after the FedAvg round."""
        p = self.pick["pod"]
        losses = []
        with self.mesh:
            for j, step_parts in enumerate(parts):
                drawn = keep is not None and j == self.pick["step"]
                before = state if drawn else None
                state, metrics = self.fed.local_step(
                    state, self.fed.batch(step_parts))
                losses.append(np.asarray(metrics["loss"]))
                if drawn:
                    keep["before"] = host_pod(before, p)
                    keep["after"] = host_pod(state.params, p)
                    before = None
            if keep is not None:
                keep["pre_round"] = state.params
            state = jax.block_until_ready(self.round(state, self.weights))
        if keep is not None:
            keep["post_round"] = state.params
        return state, np.stack(losses)

    def record(self, inputs, out) -> int:
        """Keep a window call's losses for the check; its work, in FL
        rounds."""
        self.losses[inputs[0]] = out
        return 1

    def counters(self) -> dict:
        fed = getattr(self, "fed", None)
        return fed.counters() if fed is not None else {}

    def scope_tables(self):
        """``{module: {instruction: scope or ""}}`` of the cell's compiled
        programs, served from the compile cache where one is on: call it
        after the window, never on the timed path."""
        if getattr(self, "_tables", None) is not None:
            return self._tables
        from repro.obs.scopes import hlo_scopes

        scopes = ("ssd_scan", "ssd_proj", "optimizer", "fedavg")
        st = self.state_shapes
        batch = {k: jax.ShapeDtypeStruct(
            (self.pods, self.batch_size, self.seq_len), jnp.int32,
            sharding=self.fed.batch_sharding) for k in ("tokens", "labels")}
        with self.mesh:
            lowered = {"jit_fed_step": self.fed.step.lower(st, batch),
                       "jit_fed_round": self.round.program.lower(
                           st, self.weights)}
            texts = {m: low.compile().as_text() for m, low in lowered.items()}
        self._tables = {}
        for m, text in texts.items():
            table = {op: "" for op in _instructions(text)}
            table.update(hlo_scopes(text, scopes))
            self._tables[m] = table
        return self._tables

    # -- check -----------------------------------------------------------

    def capture(self):
        """What the check needs of the drawn call, as :meth:`fl_round`
        keeps it, with the call's losses: the run replayed from the
        seed, once the window's state is freed."""
        self.state = None
        state, keep = self.init_state(), {}
        for index in range(self.pick["call"] + 1):
            drawn = index == self.pick["call"]
            state, losses = self.fl_round(state, self.inputs(index)[1],
                                          keep if drawn else None)
        return dict(keep, losses=losses)

    def check(self, limits: dict):
        """``(checks, failed)`` once the window has closed: the drawn
        step's loss and change, and the drawn round, against the
        reference."""
        bad = sum(int(not np.isfinite(x).all()) for x in self.losses.values())
        loss_gap = step_gap = round_gap = float("inf")
        call, j, p = (self.pick[k] for k in ("call", "step", "pod"))
        if call not in self.losses:
            bad += 1              # the drawn call is not in the window
        else:
            k = self.capture()
            bad += int(not np.array_equal(k["losses"], self.losses[call]))
            count = self.steps * call + j   # the pod's steps before it
            before = k["before"]
            bad += int(int(np.asarray(before.opt.step)) != count)
            batch = self.inputs(call)[1][j][p]
            dev = jax.devices()[0]
            params = jax.device_put(before.params, dev)
            want, grads = ref.loss_and_grads(
                params, batch["tokens"], batch["labels"],
                block=self.traffic["ref_block"])
            loss_gap = abs(float(k["losses"][j, p]) - want) / abs(want)
            new, _, _ = ref.adamw(params, grads,
                                  jax.device_put(before.opt.mu, dev),
                                  jax.device_put(before.opt.nu, dev),
                                  count + 1, self.lr(count), self.hp)
            step_gap = float(np.max(_step_gaps(
                params, jax.device_put(k["after"], dev), new)))
            round_gap, unsynced = self.round_gaps(k["pre_round"],
                                                  k["post_round"])
            bad += unsynced
        checks = {
            "loss_rel_gap": {"value": loss_gap,
                             "limit": limits["loss_rel_gap"]},
            "step_rel_gap": {"value": step_gap,
                             "limit": limits["step_rel_gap"]},
            "round_rel_gap": {"value": round_gap,
                              "limit": limits["round_rel_gap"]},
            "mismatches": {"value": bad, "limit": limits["mismatches"]},
        }
        failed = int(any(c["value"] > c["limit"] for c in checks.values()))
        return checks, failed

    def round_gaps(self, pre, post):
        """``(gap, unsynced)``: the largest over pods ``q`` of
        ``|post_q - R| / |R - pre_q|`` over all parameters, ``R`` the
        reference's int8 FedAvg of ``pre`` (so a round that changed
        nothing reads 1), computed one leaf at a time on the first
        chip; and the pods whose copy differs from pod 0's."""
        dev = jax.devices()[0]
        pods = [jax.tree.leaves(pod_tree(pre, q)) for q in range(self.pods)]
        outs = [jax.tree.leaves(pod_tree(post, q))
                for q in range(self.pods)]
        w = jax.device_put(self.weights, dev)
        sums = np.zeros((3, self.pods))
        for i in range(len(pods[0])):
            xs = [jax.device_put(x[i], dev) for x in pods]
            ys = [jax.device_put(y[i], dev) for y in outs]
            sums += np.asarray(_round_sums(xs, ys, w))
        err, chg, diff = sums
        gaps = np.sqrt(err / np.maximum(chg, 1e-300))
        return float(np.max(gaps)), int(np.sum(diff > 0))


@jax.jit
def _step_gaps(before, after, want):
    """Per leaf, ``|after - want| / |want - before|``: the program's
    change of the leaf against the reference's."""
    def gap(x, y, r):
        return (jnp.sqrt(jnp.sum((y - r) ** 2))
                / jnp.maximum(jnp.sqrt(jnp.sum((r - x) ** 2)), 1e-30))

    return jnp.stack(jax.tree.leaves(jax.tree.map(gap, before, after, want)))


@jax.jit
def _round_sums(xs, ys, w):
    """Per pod, for one leaf: ``|y_q - R|^2``, ``|R - x_q|^2`` and
    ``sum |y_q - y_0|``, ``R`` the reference FedAvg of the ``xs``."""
    avg = ref.fedavg_int8(xs, w)
    return jnp.stack([
        jnp.stack([jnp.sum((y - avg) ** 2) for y in ys]),
        jnp.stack([jnp.sum((avg - x) ** 2) for x in xs]),
        jnp.stack([jnp.sum(jnp.abs(y - ys[0])) for y in ys]),
    ])


class Control(Cell):
    """The control of the check: the reference with bfloat16 matrix
    products, the precision below the configuration's float32, in the
    program's place: each pod's local AdamW steps at the scheduled rate
    and the int8 FedAvg round, one pod per chip.  It has nothing to warm
    up, so it checks the window's first call, and keeps what the check
    needs as it runs."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.pick["call"] = 1
        self.kept = None

    def warmup(self) -> None:
        from repro.models import lm

        self.cfg = model_config(self.config)
        self.weights = jnp.asarray(self.traffic["pod_weights"], jnp.float32)
        params = lm.init_params(jax.random.PRNGKey(self.key), self.cfg)
        self.devs = jax.devices()[:self.pods]
        self.state = []
        for d in self.devs:
            p = jax.device_put(params, d)
            zeros = jax.tree.map(jnp.zeros_like, p)
            self.state.append((p, zeros, zeros))
        self.count = self.steps          # the warm-up call's steps

    def run(self, inputs):
        index, parts = inputs
        keep = {} if index == self.pick["call"] else None
        losses = []
        for j, step_parts in enumerate(parts):
            row = []
            for q, (p, mu, nu) in enumerate(self.state):
                b = step_parts[q]
                val, g = ref.loss_and_grads(p, b["tokens"], b["labels"],
                                            self.traffic["ref_block"],
                                            low=True)
                self.state[q] = ref.adamw(p, g, mu, nu, self.count + 1,
                                          self.lr(self.count), self.hp)
                row.append(val)
                if keep is not None and (j, q) == (self.pick["step"],
                                                   self.pick["pod"]):
                    # a pod's state as the program's TrainState has it
                    keep["before"] = SimpleNamespace(
                        params=p, opt=SimpleNamespace(step=self.count,
                                                      mu=mu, nu=nu))
                    keep["after"] = self.state[q][0]
            self.count += 1
            losses.append(np.asarray(row))
        pre = [s[0] for s in self.state]
        dev = self.devs[0]
        avg = ref.fedavg_int8(
            [jax.device_put(p, dev) for p in pre],
            jax.device_put(self.weights, dev), low=True)
        self.state = [(jax.device_put(avg, d), mu, nu)
                      for d, (_, mu, nu) in zip(self.devs, self.state)]
        if keep is not None:
            self.kept = dict(keep, pre_round=pre,
                             post_round=[s[0] for s in self.state])
        return np.stack(losses)

    def capture(self):
        return dict(self.kept, losses=self.losses[self.pick["call"]])


def scope_seconds(summary, modules, scope=None):
    """Device seconds of a profiler trace's ops (``bench.trace.Summary``)
    that belong to the current cell's programs ``modules`` (of
    ``PROGRAMS``) and, with ``scope``, to that named scope; ``None``
    where there is no cell or no such op.  A trace names an op by its
    HLO instruction alone, so the instructions are read from each
    program's compiled text (``repro.obs.scopes``).  A name that a
    program outside ``modules`` shares is left out, since its time
    cannot be split between them; so are loops and other ops whose
    events hold their body's ops."""
    if not CURRENT or not hasattr(CURRENT[0], "fed"):
        return None
    tables = CURRENT[0].scope_tables()
    if tables is None or not set(modules) <= set(tables):
        return None
    names = {op for m in modules for op, s in tables[m].items()
             if scope is None or s == scope}
    names -= {op for m, t in tables.items() if m not in modules for op in t}
    total = 0.0
    for key, sec in summary.ops.items():
        name, _, opcode = key.partition(" ")
        if name in names and opcode.strip("()") not in CONTAINERS:
            total += sec
    return total or None


def _instructions(text: str):
    """Every instruction named in an HLO module's text."""
    import re

    return re.findall(r"^\s+(?:ROOT )?%?([\w.-]+) = ", text, re.M)
