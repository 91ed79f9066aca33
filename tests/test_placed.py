"""A graph placed one partition per device (`Graph.place`) through the
public entries, and the unplaced path left as it was.

The placed checks run tests/placed_check.py once, in a subprocess with 4
simulated host devices (XLA_FLAGS must be set before jax initialises, and
the main pytest process keeps seeing one device); each test below asserts
on its part of the script's report.  The last test runs here: `pregel`'s
jitted step on an unplaced graph lowers to the program it lowered to
before placement existed, `_superstep` jitted alone.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import Graph, algorithms as alg
from repro.core.pregel import _superstep, superstep_jit
from repro.data import rmat, symmetrize

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "placed_check.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"placed_check failed\n--- stdout ---\n{proc.stdout}"
        f"\n--- stderr ---\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_each_partition_on_its_own_device(report):
    assert report["ex"] == "SpmdExchange" and report["num_devices"] == 4
    assert report["shard_devices"] == [0, 1, 2, 3]
    assert report["shard_rows"] == [1]
    assert report["pr_result_placed"]


def test_pagerank_matches_reference_and_unplaced_run(report):
    assert report["pr_rel_err_reference"] <= 1e-5
    assert report["pr_rel_err_unplaced"] <= 1e-6
    assert report["pr_supersteps"] == [10, 10]
    assert report["degrees_equal"]


def test_cc_and_sssp_are_exact(report):
    assert report["cc_mismatches"] == 0
    assert report["sssp_mismatches"] == 0
    assert report["sssp_supersteps"] == report["sssp_depth"] + 1


def test_kernel_sweep_on_placed_rows_matches_unplaced(report):
    """PageRank and WCC through the Pallas sweep (interpret mode): each
    device holds its own slice of the static slot rows, and the answers
    are the unplaced graph's."""
    p, n_chunks, eb = report["rows_full_shape"]
    assert report["rows_shard_shape"] == [[1, n_chunks, eb]]
    assert p == 4 and n_chunks > 1
    assert report["kernel_pr_rel_err_unplaced"] <= 1e-6
    assert report["kernel_cc_mismatches_unplaced"] == 0
    a, b = report["kernel_cc_supersteps"]
    assert a == b > 1


def test_step_exchanges_by_all_to_all_without_retracing(report):
    assert report["step_all_to_all"]
    # the cold first superstep and the warm rest: no program per superstep
    assert report["pr_programs"] == [2, 2]


def test_spans_carry_devices_and_bytes_crossing(report):
    assert report["span_devices"] == {"graphx.place": 4,
                                      "graphx.algorithm": 4,
                                      "graphx.pregel": 4}
    crossing = report["sync_bytes_crossing"]
    assert len(crossing) == 3 and all(c and c > 0 for c in crossing)
    assert crossing == report["metrics_bytes_crossing"]
    # a dense all-to-all keeps each partition's block to itself: 3 of 4
    assert crossing == [int(b * 3 / 4)
                        for b in report["metrics_bytes_shipped"]]


@pytest.mark.parametrize("option", ["working_set_frac", "checkpoint",
                                    "pregel_fused"])
def test_placed_graph_refuses(report, option):
    assert report["refuses"][option] is True


def test_partitioner_sweep_through_the_placed_step(report):
    cells = report["bcast_check"]
    assert cells["2d-dense"]["all_gather_ops"] == 0
    assert cells["hybrid+bcast"]["all_gather_ops"] == 1
    assert (cells["hybrid+bcast"]["all_to_all_bytes"]
            < cells["2d-dense"]["all_to_all_bytes"])


@pytest.mark.parametrize("kernel_mode", ["ref", "unfused"])
def test_unplaced_step_lowers_as_before(kernel_mode):
    gd = symmetrize(rmat(6, 4, seed=1))
    g = Graph.from_edges(gd.src, gd.dst, num_partitions=4)
    g = alg.attach_out_degree(g, kernel_mode).mapV(
        lambda vid, v: {**v, "pr": jnp.float32(1.0)})
    kw = dict(vprog=lambda vid, v, msg: {**v, "pr": 0.15 + 0.85 * msg["m"]},
              send_msg=lambda sv, ev, dv: {"m": sv["pr"] / sv["deg"]},
              gather="sum", default_msg={"m": jnp.float32(0.0)},
              skip_stale=None, changed_fn=None, kernel_mode=kernel_mode,
              payload_bound=None, fuse_apply="auto")

    def pregel_superstep(g, tstate=None, *, transport=None):
        return _superstep(g, tstate, use_cache=True, transport=transport,
                          **kw)
    before = jax.jit(pregel_superstep, static_argnames=("transport",))
    now = superstep_jit(incremental=True, **kw)
    assert now.lower(g).as_text() == before.lower(g).as_text()
    g2, _, _ = now(g)        # the warm view's program too
    assert now.lower(g2).as_text() == before.lower(g2).as_text()
