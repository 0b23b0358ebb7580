"""Generator for data-parallel training with OCCL gradient sync.

One unit of work is one training step of ``dp`` simulated ranks on one
chip: each rank's gradients (``make_grads_step``), their average through
``OcclGradSync.all_reduce`` (staging, daemon, read-back, unpack), and the
optimizer (``make_apply_step``), each ending on the host.

Set-up builds the trainer once from the seed (weights in one jitted call,
on the device) and drives it through its first ``check_steps`` steps,
which are the warm-up; the window continues the same trainer.  After the
window the plain reference (``bench/refs/dense_lm.py``) trains from the
same weights on the same rows, and each step's loss, the first step's
synced gradient (the output of ``OcclGradSync.all_reduce``, as the
optimizer gets it) and the parameters' change are compared leaf by leaf.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

# Published config keys -> the program's ArchConfig fields.
ARCH_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads", "head_dim": "d_head",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "rope_theta": "rope_theta"}
# Tiny widths of the CPU rehearsal.
REHEARSE_DIMS = {"num_hidden_layers": 2, "hidden_size": 64,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "head_dim": 16, "intermediate_size": 128, "vocab_size": 256}


@dataclasses.dataclass
class Job:
    ctx: dict
    dims: dict
    tr: dict
    arch: object
    grads_fn: object
    apply_fn: object
    sync: object
    norms: object
    states: list
    params0: object
    step: int = 0
    batches: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    first_grad: list = dataclasses.field(default_factory=list)
    change: list = dataclasses.field(default_factory=list)
    supersteps: list = dataclasses.field(default_factory=list)


def _dims(cfg: dict, rehearse: bool) -> dict:
    d = dict(cfg)
    if rehearse:
        d.update(REHEARSE_DIMS)
    return d


def make_params(abstract, key, n_layers: int):
    """Weights in the program's layout from one key, in one jitted call:
    norm scales zero (the ``1 + w`` convention), output projections
    ``0.02 / sqrt(2 L)``, every other matrix ``0.02``."""
    import jax
    import jax.numpy as jnp

    paths = jax.tree_util.tree_flatten_with_path(abstract)[0]
    treedef = jax.tree_util.tree_structure(abstract)

    def gen(key):
        out = []
        for i, (path, leaf) in enumerate(paths):
            name = jax.tree_util.keystr(path)
            if name.endswith("norm']"):
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
                continue
            std = 0.02 / np.sqrt(2 * n_layers) if name.endswith(
                ("'wo']", "'wd']")) else 0.02
            out.append((jax.random.normal(jax.random.fold_in(key, i),
                                          leaf.shape, jnp.float32)
                        * std).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(gen)(key)


def _batch(seed: int, step: int, rows: int, seq: int, vocab: int):
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 step])
    toks = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def setup(ctx: dict) -> Job:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.train import occl_sync, step as train_step
    from repro.train.state import TrainState

    tr = dict(ctx["traffic"])
    if ctx["rehearse"]:
        tr.update(tr["rehearse"])
    dims = _dims(ctx["config"], ctx["rehearse"])
    arch = dataclasses.replace(
        get_config(ctx["config"]["program_arch"]),
        **{f: dims[k] for k, f in ARCH_KEYS.items()})
    abstract = jax.eval_shape(lambda: build_model(arch).init(0))
    key = jax.random.wrap_key_data(
        jnp.asarray(ctx["seed_words"][:2], jnp.uint32))
    params = make_params(abstract, key, arch.n_layers)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    state = TrainState(params, zeros(params), zeros(params),
                       jnp.zeros((), jnp.int32))
    opt = AdamWConfig(**tr["optimizer"])
    job = Job(ctx=ctx, dims=dims, tr=tr, arch=arch,
              grads_fn=jax.jit(train_step.make_grads_step(arch)),
              apply_fn=jax.jit(train_step.make_apply_step(arch, opt)),
              sync=occl_sync.OcclGradSync(
                  abstract, tr["dp"], slice_elems=tr["slice_elems"],
                  burst_slices=tr["burst_slices"]),
              norms=jax.jit(lambda t: jnp.stack([
                  jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                  for x in jax.tree_util.tree_leaves(t)])),
              states=[state] * tr["dp"], params0=params)
    for _ in range(tr["check_steps"]):
        job.losses.append(run_step(job))
    job.change = [np.asarray(job.norms(jax.tree_util.tree_map(
        lambda a, b: a - b, s.params, params))) for s in job.states]
    return job


def run_step(job: Job) -> float:
    """One training step through the timed path; returns the rank-mean
    loss.  The rows of step ``i`` come from ``(seed, i)`` and differ in
    every step and on every rank."""
    import jax

    tr, spans, dp = job.tr, job.ctx["spans"], job.tr["dp"]
    rows = tr["rows_per_rank"]
    tok, tgt = _batch(job.ctx["seed"], job.step, dp * rows, tr["seq"],
                      job.dims["vocab_size"])
    if job.step < tr["check_steps"]:
        job.batches.append((tok, tgt))
    with spans("grads"):
        out = [job.grads_fn(job.states[r],
                            {"tokens": tok[r * rows:(r + 1) * rows],
                             "targets": tgt[r * rows:(r + 1) * rows]})
               for r in range(dp)]
        jax.block_until_ready(out)
    before = int(np.asarray(job.sync.occl.state.supersteps).max())
    with spans("sync"):
        synced = job.sync.all_reduce([g for _, g in out])
    job.supersteps.append(
        int(np.asarray(job.sync.occl.state.supersteps).max()) - before)
    if job.step == 0:
        job.first_grad = [np.asarray(job.norms(g)) for g in synced]
    with spans("apply"):
        job.states = [job.apply_fn(job.states[r], synced[r])
                      for r in range(dp)]
        jax.block_until_ready(job.states)
    job.step += 1
    return float(np.mean([float(loss) for loss, _ in out]))


def window(job: Job, seconds: float | None = None,
           units: int | None = None) -> dict:
    """Whole steps until ``seconds`` have passed (or ``units`` steps)."""
    t0 = time.perf_counter()
    unit_s, failed = [], 0
    while True:
        t = time.perf_counter()
        try:
            run_step(job)
        except Exception as e:             # a failed step ends the window
            failed = 1
            print(f"step {job.step} failed: {e!r}", file=sys.stderr)
            break
        unit_s.append(time.perf_counter() - t)
        if (units is not None and len(unit_s) >= units) or (
                units is None and time.perf_counter() - t0 >= seconds):
            break
    t1 = time.perf_counter()
    n = len(unit_s)
    tokens = n * job.tr["dp"] * job.tr["rows_per_rank"] * job.tr["seq"]
    return {"units": n, "failed": failed, "t0": t0, "t1": t1,
            "window_s": t1 - t0, "unit_s": unit_s, "tokens": tokens}


def counters(job: Job, win: dict) -> dict:
    """Daemon supersteps per step of the window (runtime counter)."""
    n = win["units"]
    return {"supersteps_per_unit": float(np.mean(job.supersteps[-n:]))
            if n else 0.0}


def work(job: Job) -> dict:
    """Operations and bytes per step, computed from shapes."""
    from bench import work as W

    tr = job.tr
    rows = tr["dp"] * tr["rows_per_rank"]
    pairs = [(b.total, b.total) for b in job.sync.buckets] * tr["dp"]
    return {"flops_per_unit": W.train_flops(job.dims, rows, tr["seq"]),
            "staging_bytes_per_unit": W.staging_bytes(pairs, 4)}


def _gap(prog, ref, keep=None) -> float:
    """Worst leaf: |norm_program - norm_reference| over the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    ref = np.asarray(ref, np.float64)
    floor = np.median(ref)
    keep = np.ones(ref.size, bool) if keep is None else keep
    return max(float(np.max((np.abs(np.asarray(p, np.float64) - ref)
                             / np.maximum(ref, floor))[keep]))
               for p in prog)


def readings(job: Job) -> dict:
    """What the program produced in its first steps: losses, and per rank
    the per-leaf norms of the first synced gradient and of the change."""
    return {"losses": np.array(job.losses), "first_grad": job.first_grad,
            "change": job.change}


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The three numbers that decide ``correct``, each beside its limit."""
    loss_gap = float(np.max(np.abs(prog["losses"] - ref["losses"])
                            / np.abs(ref["losses"])))
    # Leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: they are left out of the change.
    keep = ref["first_grad"] >= 1e-3 * np.median(ref["first_grad"])
    vals = {"loss_gap": loss_gap,
            "grad_gap": _gap(prog["first_grad"], ref["first_grad"]),
            "change_gap": _gap(prog["change"], ref["change"], keep)}
    return [{"name": k, "value": v, "limit": limits[k],
             "ok": bool(v <= limits[k])} for k, v in vals.items()]


def reference_dims(dims: dict) -> dict:
    return {"heads": dims["num_attention_heads"],
            "kv_heads": dims["num_key_value_heads"],
            "head_dim": dims["head_dim"], "eps": dims["rms_norm_eps"],
            "rope_theta": dims["rope_theta"], "vocab": dims["vocab_size"],
            "layers": dims["num_hidden_layers"]}


def release(job: Job) -> None:
    """Free the program's state before the reference runs."""
    job.states = job.sync = job.grads_fn = job.apply_fn = job.norms = None
    gc.collect()


def reference(job: Job, batches=None, fp8: bool = False) -> dict:
    """The plain reference trained from the same weights over ``batches``
    (default: the rows of the first steps)."""
    from bench.refs import dense_lm

    return dense_lm.run(job.params0, batches or job.batches,
                        reference_dims(job.dims), job.tr["optimizer"],
                        fp8=fp8)


def check(job: Job) -> tuple:
    """Frees the program's state, runs the reference, compares."""
    release(job)
    ref = reference(job)
    return (compare(readings(job), ref, job.tr["limits"]),
            {"reference_losses": ref["losses"].tolist(),
             "reference_first_grad_norm": ref["first_gnorm"]})


def _values(checks) -> dict:
    return {c["name"]: c["value"] for c in checks}


def calibrate(job: Job, control: bool) -> dict:
    """The readings the limits are set from (``bench/calibrate.py``): the
    program's after its first steps and, with ``control``, the reference
    computed one precision lower (fp8 products for bf16 training) and the
    faults planted in the reference (half the batch left out, which on two
    ranks is also each rank keeping its own gradient; one token altered
    where the rows are made; the ranks' gradients summed, not averaged;
    the state left unchanged), each put in the program's place."""
    lim = job.tr["limits"]
    release(job)
    ref = reference(job)
    out = {"program": _values(compare(readings(job), ref, lim)),
           "reference_first_grad_norm": ref["first_gnorm"]}
    if control:
        def as_prog(r):
            return {"losses": r["losses"], "first_grad": [r["first_grad"]],
                    "change": [r["change"]]}
        out["control_fp8"] = _values(compare(
            as_prog(reference(job, fp8=True)), ref, lim))
        half = [(t[:t.shape[0] // 2], g[:g.shape[0] // 2])
                for t, g in job.batches]
        out["fault_half_batch"] = _values(compare(
            as_prog(reference(job, batches=half)), ref, lim))
        altered = [(t.copy(), g) for t, g in job.batches]
        for t, _ in altered:
            t[0, 0] = (t[0, 0] + 1) % job.dims["vocab_size"]
        out["fault_token"] = _values(compare(
            as_prog(reference(job, batches=altered)), ref, lim))
        # A sync that sums the ranks' gradients instead of averaging
        # them: the optimizer gets dp times the gradient; its clipping
        # and Adam's normalisation leave the losses and the change.
        out["fault_sum_not_mean"] = _values(compare(
            {"losses": ref["losses"],
             "first_grad": [job.tr["dp"] * ref["first_grad"]],
             "change": [ref["change"]]}, ref, lim))
        out["fault_state_unchanged"] = _values(compare(
            {"losses": np.repeat(ref["losses"][:1], len(ref["losses"])),
             "first_grad": [ref["first_grad"]],
             "change": [np.zeros_like(ref["change"])]}, ref, lim))
    return out
