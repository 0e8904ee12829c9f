"""The GCN cell: its graph has ogbn-arxiv's counts, the program's step
agrees with the plain reference and one bfloat16 pass does not, the
control and three bfloat16 passes are not correct at the cell's limits,
and a tiny cell run through the harness is correct while a broken step is
not."""
import dataclasses
import json
import os

import numpy as np
import pytest

from bench import datagen, gcn_control, harness
from bench_tiny import ROOT, make_root, run_tiny

with open(os.path.join(ROOT, "bench", "configs", "gcn-arxiv.json")) as f:
    COMMITTED = json.load(f)

# The committed configuration at a size the Pallas interpreter runs in
# milliseconds a step; its limits stay the committed ones.
TINY = dict(COMMITTED, name="tiny-gcn", rows=96, cols=96, nnz=400,
            nnz_per_row_std=3.0, local_window=8, split=[48, 24, 24],
            in_dim=16, hidden=32, num_classes=8)

# One fp32 step of the program against the reference, on the CPU, as the
# check measures it (relative norms, the largest over the parameters).
# Both sum in fp32 in different orders (kernel panels against segment
# sums), so the loss, the gradient and Adam's update agree to a few float32
# ulps (read: up to 1.2e-7, 6.6e-7 and 9e-7); at this size no ReLU input
# falls within rounding of zero.  Ten times those readings, roughly; one
# bfloat16 pass reads 2e-5 and 3e-2 and more.
TOLERANCES = {"loss_rel_err": 1e-6, "grad_err": 1e-5, "update_err": 1e-5}


@pytest.fixture(scope="module")
def gcn_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("gcn"),
                     configs={"tiny-gcn": TINY},
                     cells={"tiny-gcn-train": ("tiny-gcn", "train_closed")})


@pytest.fixture(scope="module")
def tiny_system(gcn_root):
    bench = harness.Bench(gcn_root)
    cfg = bench.config("tiny-gcn")
    system = bench.system("gcn").System(cfg, bench.traffic("train_closed"),
                                        1)
    system.build(harness.Clock())
    return system


def test_the_cells_graph_has_ogbn_arxivs_counts():
    pub = COMMITTED["published"]
    m = datagen.matrix(COMMITTED)
    assert m.shape == (pub["nodes"], pub["nodes"]) == (169_343, 169_343)
    assert m.nnz == pub["edges"] == 1_166_243
    lin = m.rows.astype(np.int64) * m.shape[1] + m.cols
    assert (lin[1:] > lin[:-1]).all()            # sorted, no duplicates
    assert sum(COMMITTED["split"]) == pub["nodes"]


def test_the_cell_runs_the_registered_configuration():
    """The cell's widths and optimizer are those the repository registers,
    so the system's replacement of them changes nothing."""
    from repro.configs import gcn_arxiv
    from repro.models import gcn
    cfg = gcn_arxiv.CONFIG
    assert (cfg.num_layers, cfg.in_dim, cfg.hidden, cfg.num_classes) == (
        COMMITTED["num_layers"], COMMITTED["in_dim"], COMMITTED["hidden"],
        COMMITTED["num_classes"])
    assert (cfg.dropout, cfg.bn_eps, cfg.precision) == (
        COMMITTED["dropout"], COMMITTED["bn_eps"], COMMITTED["precision"])
    opt = gcn.opt_config(cfg)
    adam = COMMITTED["adam"]
    assert (opt.lr, opt.beta1, opt.beta2, opt.eps, opt.weight_decay) == (
        adam["lr"], adam["beta1"], adam["beta2"], adam["eps"],
        adam["weight_decay"])
    assert gcn_arxiv.GRAPH["nodes"] == COMMITTED["published"]["nodes"]
    assert gcn_arxiv.GRAPH["edges"] == COMMITTED["published"]["edges"]


def test_the_transpose_holds_the_graphs_entries():
    """On the cell's graph Aᵀ stores Â's 2,500,199 entries (it had 7.4
    times as many with A's tile padding), counted by
    ``loops.transpose_entries`` under the ``loops.transpose`` span."""
    from repro.models import gcn
    from repro.obs import Obs, set_active
    m = datagen.matrix(COMMITTED)
    adj = gcn.normalized_adjacency(m.rows, m.cols, m.shape[0])
    obs = Obs()
    prev = set_active(obs)
    try:
        g = gcn.prepare_graph(adj)
    finally:
        set_active(prev)
    counted = [i.value for kind, i in obs.metrics.instruments()
               if kind == "counter" and i.name == "loops.transpose_entries"]
    assert adj.nnz == 2_500_199
    assert counted == [adj.nnz] == [g.adj.transposed().n_slots]
    assert [e["name"] for e in obs.sink.events].count("loops.transpose") == 1


@pytest.mark.parametrize("seed", [3, 2**33 + 5])
def test_the_step_matches_the_reference_and_bf16_does_not(tiny_system,
                                                          seed):
    """Through ``build_train_step``: the loss, the gradient the step
    applied and its AdamW update agree with the plain reference within
    ``TOLERANCES``; the reference at one bfloat16 pass fails them."""
    before, after, loss, key = gcn_control.checked_step(tiny_system, seed)
    p0, o0 = before
    want = tiny_system.reference_step(p0, o0, key)
    got = tiny_system.errors(
        p0, tiny_system.program_step(before, after, loss), want)
    assert all(got[k] <= TOLERANCES[k] for k in TOLERANCES), got
    bf16 = tiny_system.errors(
        p0, tiny_system.reference_step(p0, o0, key, "default"), want)
    assert any(bf16[k] > TOLERANCES[k] for k in TOLERANCES), bf16


@pytest.mark.parametrize("side", ["control", "high"])
def test_the_control_is_not_correct_at_the_cells_limits(tiny_system, side):
    """Put in the program's place and judged by ``System.check`` against
    the committed limits, as ``bench/gcn_control.py`` judges it: one
    bfloat16 pass (the control) is not correct, nor are three passes, which
    the output layer's gradient gives away; the program's own step is."""
    assert tiny_system.cfg["limits"] == COMMITTED["limits"]
    program = gcn_control.judged(tiny_system, 6, "program")
    assert all(v <= lim for v, lim in program.values()), program
    checks = gcn_control.judged(tiny_system, 6, side)
    assert not all(v <= lim for v, lim in checks.values()), checks
    v, lim = checks["head_grad_err"]
    assert v > lim, checks


def test_a_sound_run_is_correct(gcn_root):
    r = run_tiny(gcn_root, "tiny-gcn-train", seed=2**33 + 11, seconds=1.0)
    assert r["correct"], r["checks"]
    assert set(r["checks"]) >= {"loss_rel_err", "grad_err", "update_err",
                                "head_grad_err"}


def _broken_adjacency(monkeypatch):
    """The program's Â with its entries 1% too large."""
    from repro.models import gcn
    real = gcn.normalized_adjacency

    def wrong(rows, cols, n):
        a = real(rows, cols, n)
        return dataclasses.replace(a, vals=a.vals * np.float32(1.01))
    monkeypatch.setattr(gcn, "normalized_adjacency", wrong)


def test_a_broken_step_is_not_correct(gcn_root, monkeypatch):
    _broken_adjacency(monkeypatch)
    r = run_tiny(gcn_root, "tiny-gcn-train", seed=4, seconds=1.0)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_a_run_that_never_reaches_its_checked_step_is_not_correct(
        gcn_root):
    def no_window(system):
        start = system.start

        def late(seed):
            start(seed)
            system.check_step = 10**9
        system.start = late
    r = run_tiny(gcn_root, "tiny-gcn-train", seed=5, seconds=0.5,
                 hook=no_window)
    assert not r["correct"]
