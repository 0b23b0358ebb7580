"""Operation and byte counts behind train_step_mfu and staging_roofline,
pinned on the qwen3-0.6b cut, and the table of peaks."""
import json

import pytest

from bench import harness, peaks, work

QWEN = json.loads((harness.BENCH / "configs" / "qwen3-0.6b.json").read_text())


def test_param_count_of_the_cut():
    assert work.param_count(QWEN) == 102_246_400


def test_param_count_matches_the_program():
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model

    arch = dataclasses.replace(get_config("qwen3-0.6b"), n_layers=2,
                               vocab=1000)
    dims = dict(QWEN, num_hidden_layers=2, vocab_size=1000)
    shapes = jax.eval_shape(lambda: build_model(arch).init(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert work.param_count(dims) == n


def test_train_flops_of_the_cut():
    # 6 x 82,362,368 matrix parameters x 4,096 tokens, plus causal
    # attention: 3 x 4 layers x 4 rows x 2 x 1024^2 x 16 heads x 128.
    assert work.train_flops(QWEN, rows=4, seq=1024) == 2_230_295_986_176


def test_staging_bytes_of_the_13_buckets():
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.train.occl_sync import OcclGradSync

    arch = dataclasses.replace(get_config("qwen3-0.6b"), n_layers=4,
                               vocab=18992)
    shapes = jax.eval_shape(lambda: build_model(arch).init(0))
    sync = OcclGradSync(shapes, 2, slice_elems=65536, burst_slices=8)
    sizes = [b.total for b in sync.buckets]
    assert len(sizes) == 13 and sum(sizes) == 102_246_400
    pairs = [(n, n) for n in sizes] * 2
    assert work.staging_bytes(pairs, 4) == 3_271_884_800


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
