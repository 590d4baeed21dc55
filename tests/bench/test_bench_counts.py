"""Counts the benchmark computes from shapes: the network's dense FLOPs
and the point-op kernels' operations and bytes."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def test_dense_flops_of_one_s3dis_block():
    from bench import model
    f = model.dense_flops(model.load("pointnet2_ssg_seg_s3dis"), 4096)
    # By hand, two FLOPs per multiply-add:
    # SA1 1024 centres x 32 neighbours x (6*32 + 32*32 + 32*64)
    assert f.sa[0] == 1024 * 32 * 2 * (6 * 32 + 32 * 32 + 32 * 64)
    # SA4 16 x 32 x (259*256 + 256*256 + 256*512)
    assert f.sa[3] == 16 * 32 * 2 * (259 * 256 + 256 * 256 + 256 * 512)
    # FP1 on the 64 points of stage 3: (512 + 256) -> 256 -> 256
    assert f.fp[0] == 64 * 2 * (768 * 256 + 256 * 256)
    # FP4 on all 4096 points: (128 + 3) -> 128 -> 128 -> 128
    assert f.fp[3] == 4096 * 2 * (131 * 128 + 128 * 128 + 128 * 128)
    assert f.head == 4096 * 2 * (128 * 128 + 128 * 13)
    assert f.total == 1_930_690_560          # 1.93 GFLOP


def test_dense_flops_scale_with_real_points():
    from bench import model
    cfg = model.load("pointnet2_ssg_seg_scannet")
    assert model.dense_flops(cfg, 8192).total == 2_501_115_904
    assert model.dense_flops(cfg, 4096).total < 2_501_115_904


@pytest.mark.parametrize("kind,shapes,ops,nbytes", [
    ("fps", {"nb": 2, "bs": 128, "k": 9}, 10 * 2 * 9 * 128,
     4 * 2 * (4 * 128 + 9)),
    ("ball_query", {"nb": 3, "kc": 128, "w": 256, "num": 32},
     10 * 3 * 128 * 256, 4 * 3 * (4 * 128 + 4 * 256 + 2 * 128 * 32 + 128)),
    ("knn", {"nb": 1, "q": 128, "w": 128, "k": 3}, 11 * 128 * 128,
     4 * (3 * 128 + 4 * 128 + 2 * 128 * 3)),
    ("gather", {"nb": 2, "m": 96, "c": 128}, 0, 4 * 2 * (2 * 96 * 128 + 96)),
    ("scatter_add", {"nb": 2, "m": 96, "c": 128, "w": 64}, 2 * 96 * 128,
     4 * 2 * (96 * 128 + 96 + 64 * 128)),
])
def test_point_op_counts(kind, shapes, ops, nbytes):
    from bench import pointops
    assert pointops.count(kind, shapes) == (ops, nbytes)


def test_least_time_names_its_bound():
    from bench import device, pointops
    peaks = device.PEAKS["TPU v5 lite"]
    t, bound = pointops.least_time(0.0, 819e9, peaks)
    assert (t, bound) == (1.0, "memory")
    t, bound = pointops.least_time(197e12 * 2, 1.0, peaks)
    assert (t, bound) == (2.0, "compute")
