"""GCN training cells: full-graph steps of a registered GNN configuration on
the system's normal training path, ``models.api`` under
``dist.step.build_train_step`` on a one-device mesh, with propagation by
``loops_spmm`` (its custom VJP runs the same kernels on Aᵀ).

Set-up makes the graph with ``datagen.matrix``, symmetrises and normalises
it (``models.gcn.normalized_adjacency``), converts it and builds Aᵀ
(``convert_s``; the Aᵀ build alone is ``transpose_s``, read from the
program's ``loops.transpose`` span), then compiles the step.  Features,
labels and the split come from the configuration's ``data_seed``, the
weights and each step's dropout key from the run's seed.  Each call is one
train step, whose parameters and optimizer state are the previous step's.

What is checked: one step of the window, its index drawn from the seed
among the first ``CHECK_STEPS``.  Its parameters and optimizer state are
copied before it is sent (the step donates them), and the state it leaves
before the next step is sent.  The plain reference (``gcn_reference``) runs
the same step from the copies with the same dropout key.  Compared, each
as the norm of the error over the norm of the reference's, the largest
over the parameters:

* ``loss_rel_err`` — the loss's relative error;
* ``grad_err`` — the gradient the step applied, recovered from Adam's first
  moment as ``(m_k − β1·m_{k−1}) / (1 − β1)``, against the reference's;
* ``update_err`` — the step's parameter update (new − old) against the
  reference's.  Adam's update is close to ``lr·sign(g)``, so the update
  alone would hide a wrong gradient;
* ``head_grad_err`` — ``grad_err`` over the output layer alone.

Norms, not largest entries: a ReLU input within rounding of zero passes
its gradient in one computation and not in the other, and each such kink
moves one column of a hidden layer's gradient by one node's share.  At
ogbn-arxiv's size a few kinks fall so in every fp32 step, so entrywise
errors read 3e-4 to 6e-3 and swing with which nodes they hit; the norm
grows with how many fell, which the rounding sets (PERF.md §4).  The
output layer's gradient lies past every kink, so it keeps float32's
accuracy, and ``head_grad_err`` fails a program whose products lose it
(three bfloat16 passes read several times the float32 program's there).
"""
from __future__ import annotations

import dataclasses
import sys
import time

import numpy as np

from bench import datagen, gcn_reference, work

CHECK_STEPS = 8   # the checked step is one of the window's first this many


@dataclasses.dataclass(frozen=True)
class KernelWork(work.Work):
    """The SpMM work of a step, and in ``vjp`` the part on Aᵀ."""

    vjp: work.Work = work.Work()


def _widths(cfg: dict) -> list[int]:
    return [cfg["hidden"]] * (cfg["num_layers"] - 1) + [cfg["num_classes"]]


def _is_moments(t) -> bool:
    """An optimizer leaf: a parameter's master copy and moments."""
    return isinstance(t, dict) and "m" in t


def _unflat(flat, like) -> np.ndarray:
    """A parameter's entries out of its flat optimizer leaf."""
    return np.asarray(flat).reshape(-1)[:like.size].reshape(like.shape)


class System:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix = cfg, mix
        self.seed = int(seed)

    def build(self, clock) -> None:
        """Graph, conversion and Aᵀ, then :meth:`start` at the run's seed
        and two steps, the first of which compiles."""
        import jax
        from repro.compat import make_mesh
        from repro.configs import get_config
        from repro.dist.step import build_train_step
        from repro.models import api, gcn
        from repro.obs import get_active
        cfg = self.cfg
        mcfg = dataclasses.replace(
            get_config(cfg["arch"]), num_layers=cfg["num_layers"],
            in_dim=cfg["in_dim"], hidden=cfg["hidden"],
            num_classes=cfg["num_classes"], dropout=cfg["dropout"],
            bn_eps=cfg["bn_eps"], lr=cfg["adam"]["lr"],
            weight_decay=cfg["adam"]["weight_decay"],
            precision=cfg["precision"])
        self.coo = datagen.matrix(cfg)
        n = self.coo.shape[0]
        obs = get_active()
        seen = len(obs.sink.events) if obs is not None else 0
        with clock.span("convert_s"):
            adj = gcn.normalized_adjacency(self.coo.rows, self.coo.cols, n)
            graph = gcn.prepare_graph(adj)
        if obs is not None:
            spans = [e["dur"] for e in obs.sink.events[seen:]
                     if e["name"] == "loops.transpose"]
            if spans:
                clock["transpose_s"] = sum(spans) * 1e-6
        self.nnz = adj.nnz
        self.x, self.y, self.train = node_data(cfg, n)
        self.mesh = make_mesh((1,), ("data",))
        self.init = jax.jit(lambda k: api.init_params(mcfg, k))
        params = jax.eval_shape(self.init, datagen.key(0))
        batch = gcn.make_batch(self.x, self.y, self.train,
                               jax.random.key_data(datagen.key(0)))
        self.bundle = build_train_step(
            mcfg, self.mesh, params, jax.eval_shape(lambda: batch),
            gcn.opt_config(mcfg),
            loss_fn=lambda p, mb: api.train_loss(mcfg, p, mb, graph))
        self.batch = self._place(batch, self.bundle.batch_spec)
        self.step_key = jax.jit(
            lambda k, i: jax.random.key_data(jax.random.fold_in(k, i))[None])
        self.snapshot = jax.jit(lambda t: jax.tree.map(lambda a: a.copy(), t))
        self.start(self.seed)
        t0 = time.perf_counter()
        jax.block_until_ready(self._step())
        t1 = time.perf_counter()
        jax.block_until_ready(self._step())
        clock["compile_s"] = (t1 - t0) - (time.perf_counter() - t1)
        jax.block_until_ready(self.snapshot((self.params, self.opt)))

    def _place(self, tree, spec):
        import jax
        from repro.dist import sharding as shr
        return jax.device_put(tree, shr.spec_to_sharding(spec, self.mesh))

    def start(self, seed: int) -> None:
        """Weights and a fresh optimizer state from ``seed``, its dropout
        keys, and the step it checks."""
        from repro.optim import adamw
        params = self.init(datagen.key(seed))
        self.params = self._place(params, self.bundle.param_spec)
        self.opt = self._place(adamw.init_opt_state(params, 1),
                               self.bundle.opt_spec)
        self.base_key = datagen.key(seed)
        self.steps = 0
        self.check_step = int(np.random.default_rng(seed).integers(
            0, CHECK_STEPS))
        self.before = self.after = self.checked = None

    def _step(self):
        """One train step from the current state; returns its loss and
        dropout key."""
        batch = dict(self.batch, key=self.step_key(self.base_key,
                                                   self.steps))
        self.params, self.opt, metrics = self.bundle.fn(self.params,
                                                        self.opt, batch)
        self.steps += 1
        return metrics["loss"], batch["key"]

    def dispatch(self, k: int):
        if k == self.check_step:
            self.before = self.snapshot((self.params, self.opt))
        elif k == self.check_step + 1:
            self.after = self.snapshot((self.params, self.opt))
        loss, key = self._step()
        if k == self.check_step:
            self.checked = (loss, key)
        return loss

    def done(self, k: int, out) -> None:
        pass

    def work(self):
        cfg, n = self.cfg, self.coo.shape[0]
        spmm = [work.spmm(n, n, w, self.nnz, 4, 4, 4) for w in _widths(cfg)]
        fwd = sum(spmm, work.Work())
        dense = work.Work()
        dims = [cfg["in_dim"]] + _widths(cfg)
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            mm = work.Work(2.0 * n * a * b, 4.0 * (n * a + a * b + n * b))
            # forward, the weight's gradient, and the input's gradient
            # where the input is an activation (not the features)
            dense = dense + mm + mm + (mm if i else work.Work())
        kernel = KernelWork(2 * fwd.flops, 2 * fwd.bytes, vjp=fwd)
        return kernel, work.Work(kernel.flops, kernel.bytes) + dense

    def release(self) -> None:
        if self.before is not None and self.after is None:
            self.after = (self.params, self.opt)
        del self.bundle, self.params, self.opt, self.batch

    def check(self, stand_in=None) -> dict:
        """Each check's ``(value, limit)``.  ``stand_in``, a ``(loss, grads,
        new_params)`` of the checked step, is judged in the program's place
        (the control, ``bench/gcn_control.py``)."""
        limits = self.cfg["limits"]
        if self.checked is None:
            errs = {k: float("inf") for k in limits}
        else:
            p0, o0 = self.before
            got = (self.program_step(self.before, self.after,
                                     self.checked[0])
                   if stand_in is None else stand_in)
            want = self.reference_step(p0, o0, self.checked[1])
            errs = self.errors(p0, got, want)
        return {k: (errs[k], float(limits[k])) for k in limits}

    def program_step(self, before, after, loss):
        """The program's ``(loss, grads, new_params)`` of the step from
        ``before`` to ``after``, each a ``(params, opt_state)``: the
        gradient it applied is recovered from Adam's first moment."""
        import jax
        (p0, o0), (p1, o1) = before, after
        b1 = float(self.cfg["adam"]["beta1"])
        grads = jax.tree.map(
            lambda t1, t0, p: (_unflat(t1["m"], p) - b1 * _unflat(t0["m"], p))
            / (1 - b1), o1["flat"], o0["flat"], p0, is_leaf=_is_moments)
        return loss, grads, p1

    def reference_step(self, params, opt, key, precision="highest"):
        """The reference's ``(loss, grads, new_params)`` of one step from
        a program state ``(params, opt)`` with the dropout key ``key``."""
        import jax
        m = jax.tree.map(lambda t, p: _unflat(t["m"], p), opt["flat"],
                         params, is_leaf=_is_moments)
        v = jax.tree.map(lambda t, p: _unflat(t["v"], p), opt["flat"],
                         params, is_leaf=_is_moments)
        if not hasattr(self, "graph_ref"):
            self.graph_ref = gcn_reference.chunks_of(self.coo)
        return gcn_reference.train_step(
            self.cfg, self.graph_ref, params, m, v, opt["count"], self.x,
            self.y, self.train, np.asarray(key)[0], precision)

    def errors(self, p0, got, want) -> dict:
        """The checks' numbers: ``got`` against ``want``, two ``(loss,
        grads, new_params)`` of a step from the parameters ``p0``."""
        import jax

        def rel(a, b):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return float(np.linalg.norm(a - b)
                         / max(np.linalg.norm(b), 1e-30))

        def worst(a, b):
            return max(jax.tree.leaves(jax.tree.map(rel, a, b)))

        def update(p):
            return jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                p, p0)

        head = f"conv{self.cfg['num_layers'] - 1}"
        out = {"loss_rel_err": rel(got[0], want[0]),
               "grad_err": worst(got[1], want[1]),
               "update_err": worst(update(got[2]), update(want[2])),
               "head_grad_err": worst(got[1][head], want[1][head])}
        print(f"gcn check: step {self.check_step}: {out}", file=sys.stderr)
        return out


def node_data(cfg: dict, n: int):
    """Features (standard normal, on the device), labels (uniform over the
    classes) and the train mask (the first of the published split's counts
    of a random order of the nodes), from the configuration's
    ``data_seed``."""
    import jax
    import jax.numpy as jnp
    seed = int(cfg["data_seed"])
    x = jax.random.normal(datagen.key(seed), (n, cfg["in_dim"]), jnp.float32)
    rng = np.random.default_rng(seed)
    y = rng.integers(0, cfg["num_classes"], n).astype(np.int32)
    train = np.zeros(n, np.float32)
    train[rng.permutation(n)[:int(cfg["split"][0])]] = 1.0
    return x, jnp.asarray(y), jnp.asarray(train)
