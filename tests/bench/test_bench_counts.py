"""Operation and byte counts, against shapes worked out by hand."""
import json
from pathlib import Path

import pytest

from bench.lib import counts

BENCH = Path(__file__).resolve().parents[2] / "bench"


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_causal_pairs():
    assert counts.causal_pairs(1) == 1
    assert counts.causal_pairs(4) == 10


def test_serve_plane_flops_small():
    c = {"hidden_size": 4, "intermediate_size": 6, "num_local_experts": 8,
         "num_experts_per_tok": 2, "vocab_size": 10, "num_hidden_layers": 1}
    s = 2
    want = (s * 2 * 4 * 16          # q k v o: 2 tokens x 4 x 4x4
            + 2 * 2 * 3 * 4         # scores + values over 3 pairs
            + s * 2 * 4 * 8         # router
            + s * 2 * 2 * 3 * 4 * 6  # 2 experts x 3 matrices
            + s * 2 * 4 * 10)       # unembedding
    assert counts.serve_plane_flops(c, s) == want


def test_serve_plane_flops_phi35_width():
    per_token = counts.serve_plane_flops(config("phi35moe-plane-1l"),
                                         16) / 16
    # 8 d^2 + 2 experts x 6 d f + 2 d v, plus router and scores
    assert per_token == pytest.approx(
        8 * 4096**2 + 12 * 4096 * 6400 + 2 * 4096 * 32064, rel=2e-3)
    assert per_token == pytest.approx(0.712e9, rel=1e-2)


def test_dense_lm_counts_starcoder2_width():
    c = config("starcoder2-3b-4l")
    per_layer = 3072 * 24 * 128 * 2 + 3072 * 2 * 128 * 2 + 2 * 3072 * 12288
    assert per_layer == 95_944_704
    assert counts.dense_lm_matmul_params(c) == 4 * per_layer + 49152 * 3072
    attn = 4 * 3 * 4 * (4096 * 4097 / 2) * 3072 / 4096
    assert counts.dense_lm_train_flops_per_token(c, 4096) == \
        pytest.approx(6 * (4 * per_layer + 49152 * 3072) + attn)
    assert counts.dense_lm_train_flops_per_token(c, 4096) == \
        pytest.approx(3.51e9, rel=1e-2)


def test_gather_bytes_and_roofline():
    assert counts.gather_bytes(128, 4096, 32) == \
        128 * 4 + (128 + 32) * 4096 * 4
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(1000.0, 50.0, peaks) == 10.0   # compute
    assert counts.roofline_s(10.0, 500.0, peaks) == 50.0    # bandwidth


def test_peaks_table_has_v5e():
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_importing_the_benchmark_describes_no_topology():
    """Importing every module of the benchmark loads no TPU library and
    describes no topology (only one process may hold libtpu)."""
    import subprocess
    import sys
    code = ("import sys, pkgutil, importlib, bench\n"
            "for m in pkgutil.walk_packages(bench.__path__, 'bench.'):\n"
            "    importlib.import_module(m.name)\n"
            "print('jax.experimental.topologies' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], cwd=BENCH.parent,
                         capture_output=True, text=True, timeout=120,
                         env={"PYTHONPATH": f"{BENCH.parent}:"
                              f"{BENCH.parent / 'src'}",
                              "JAX_PLATFORMS": "cpu",
                              "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
