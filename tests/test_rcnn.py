"""Standalone R-CNN head layers (reference parity: nn/RegionProposal.scala,
nn/BoxHead.scala, nn/MaskHead.scala, nn/Proposal.scala,
nn/DetectionOutputFrcnn.scala) + TableOperation/DenseToSparse/TreeLSTM tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn


def test_region_proposal_shapes_and_validity():
    rp = nn.RegionProposal(in_channels=8, anchor_sizes=(32, 64),
                           anchor_stride=(8, 16), pre_nms_top_n=50,
                           post_nms_top_n=20)
    params, state = rp.init(jax.random.PRNGKey(0))
    feats = (jnp.ones((2, 16, 16, 8)), jnp.ones((2, 8, 8, 8)))
    # (jitted, as the heads below: one program, where the eager call
    # compiles every op of the anchor, top-k and NMS loops on its own)
    (props, valid), _ = jax.jit(
        lambda p, s, f: rp.apply(p, s, f, (128, 128)))(params, state, feats)
    assert props.shape == (2, 20, 4)
    assert valid.shape == (2, 20)
    assert bool(valid.any())
    # proposals are clipped to the image
    assert float(props.min()) >= 0.0
    assert float(props.max()) <= 128.0


def test_region_proposal_requires_paired_sizes():
    with pytest.raises(AssertionError):
        nn.RegionProposal(8, anchor_sizes=(32, 64), anchor_stride=(8,))


def test_proposal_layer():
    prop = nn.Proposal(pre_nms_top_n=100, post_nms_top_n=10,
                       scales=(8,), min_size=4)
    params, state = prop.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    na = prop.anchor.num  # 3 ratios x 1 scale
    cls_prob = jnp.asarray(r.rand(1, 8, 8, 2 * na).astype(np.float32))
    bbox = jnp.asarray(0.1 * r.randn(1, 8, 8, 4 * na).astype(np.float32))
    (rois, valid), _ = jax.jit(prop.apply)(params, state, cls_prob, bbox,
                                           jnp.asarray([128.0, 128.0]))
    assert rois.shape == (1, 10, 4)
    assert bool(valid.any())


def test_box_head_end_to_end():
    bh = nn.BoxHead(in_channels=8, resolution=4, scales=(0.25, 0.125),
                    sampling_ratio=2, score_thresh=0.0, nms_thresh=0.5,
                    max_per_image=8, output_size=16, num_classes=5)
    params, state = bh.init(jax.random.PRNGKey(1))
    feats = [jnp.ones((1, 32, 32, 8)), jnp.ones((1, 16, 16, 8))]
    proposals = jnp.asarray([[0, 0, 32, 32], [8, 8, 96, 96],
                             [0, 0, 120, 120]], jnp.float32)
    (boxes, scores, labels, valid), _ = jax.jit(
        lambda p, s, f, pr: bh.apply(p, s, f, pr, (128, 128)))(
            params, state, feats, proposals)
    assert boxes.shape == (8, 4)
    assert scores.shape == labels.shape == valid.shape == (8,)
    assert bool(valid.any())
    # labels are never the background class
    assert int(labels[valid].min()) >= 1


def test_mask_head_shapes_and_range():
    mh = nn.MaskHead(in_channels=8, resolution=7, scales=(0.25,),
                     sampling_ratio=2, layers=(16, 16), dilation=1,
                     num_classes=4)
    params, state = mh.init(jax.random.PRNGKey(2))
    feats = [jnp.ones((1, 32, 32, 8))]
    boxes = jnp.asarray([[0, 0, 64, 64], [16, 16, 80, 80]], jnp.float32)
    labels = jnp.asarray([1, 3], jnp.int32)
    masks, _ = jax.jit(mh.apply)(params, state, feats, boxes, labels)
    assert masks.shape == (2, 14, 14)   # deconv doubles the resolution
    assert float(masks.min()) >= 0.0 and float(masks.max()) <= 1.0


def test_detection_output_frcnn():
    n, c = 6, 4
    r = np.random.RandomState(3)
    probs = jax.nn.softmax(jnp.asarray(r.randn(n, c).astype(np.float32)))
    deltas = jnp.asarray(0.05 * r.randn(n, 4 * c).astype(np.float32))
    rois = jnp.asarray(r.rand(n, 4).astype(np.float32) * 50)
    rois = rois.at[:, 2:].set(rois[:, :2] + 20)
    det = nn.DetectionOutputFrcnn(nms_thresh=0.3, n_classes=c,
                                  max_per_image=10, score_thresh=0.0)
    boxes, scores, labels, valid = jax.jit(det.forward)(
        {}, probs, deltas, rois, jnp.asarray([100.0, 100.0]))
    assert boxes.shape == (10, 4)
    assert bool(valid.any())
    # scores are sorted descending over the valid prefix
    s = np.asarray(scores)[np.asarray(valid)]
    assert (np.diff(s) <= 1e-6).all()


def test_table_operation_expand():
    big = jnp.arange(12, dtype=jnp.float32).reshape(2, 3, 2)
    small = jnp.asarray([[2.0], [3.0]])
    out = nn.CMulTableExpand().forward({}, (big, small))
    expected = big * small[:, :, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected))
    out2 = nn.CDivTableExpand().forward({}, (big, small))
    np.testing.assert_allclose(np.asarray(out2),
                               np.asarray(big / small[:, :, None]))


def test_dense_to_sparse_roundtrip():
    dense = np.zeros((3, 8), np.float32)
    dense[0, 2] = 1.5
    dense[1, 5] = -2.0
    dense[2, [1, 7]] = 3.0
    layer = nn.DenseToSparse(nnz_per_row=2)
    coo = layer.forward({}, dense)
    back = np.asarray(coo.to_dense())
    np.testing.assert_allclose(back, dense)


def test_tree_lstm_base_class():
    m = nn.BinaryTreeLSTM(4, 6)
    assert isinstance(m, nn.TreeLSTM)
    assert m.input_size == 4 and m.hidden_size == 6


def test_region_proposal_min_size_filters_degenerate_boxes():
    # with min_size large enough that every box is filtered, nothing may
    # come back valid (the -inf mask must survive into nms)
    rp = nn.RegionProposal(in_channels=4, anchor_sizes=(4,),
                           anchor_stride=(8,), pre_nms_top_n=16,
                           post_nms_top_n=4, min_size=10_000)
    params, state = rp.init(jax.random.PRNGKey(0))
    feats = (jnp.ones((1, 8, 8, 4)),)
    (props, valid), _ = jax.jit(
        lambda p, s, f: rp.apply(p, s, f, (64, 64)))(params, state, feats)
    assert not bool(valid.any())


def test_new_modules_serializer_roundtrip(tmp_path):
    """Round-2 modules must survive the durable format (a closure-based
    initializer once made the heads unpicklable)."""
    from bigdl_tpu.utils.serializer import load_module, save_module
    for i, build in enumerate([
        lambda: nn.BoxHead(4, 4, (0.25,), 2, 0.0, 0.5, 4, 16, 3),
        lambda: nn.RegionProposal(4, (32,), (0.5, 1.0), (8,), 16, 8),
        lambda: nn.MaskHead(4, 4, (0.25,), 2, (8,), 1, 3),
        lambda: nn.TableOperation(nn.CMulTable()),
    ]):
        m = build()
        p, s = m.init(jax.random.PRNGKey(i))
        path = str(tmp_path / f"m{i}.bigdl-tpu")
        save_module(path, m, p, s)
        m2, p2, s2 = load_module(path)
        assert type(m2).__name__ == type(m).__name__
        l1 = jax.tree.leaves(p)
        l2 = jax.tree.leaves(p2)
        assert len(l1) == len(l2)
        for a, b in zip(l1, l2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_region_proposal_traced_im_info_under_jit():
    """ADVICE r2: a traced im_info operand must not hit int() — the heads
    promise one XLA program, so clipping has to work on traced scalars."""
    rp = nn.RegionProposal(in_channels=4, anchor_sizes=(32,),
                           anchor_stride=(8,), pre_nms_top_n=20,
                           post_nms_top_n=8)
    params, state = rp.init(jax.random.PRNGKey(0))
    feats = (jnp.ones((1, 8, 8, 4)),)

    @jax.jit
    def run(p, s, f, hw):
        (props, valid), _ = rp.apply(p, s, f, hw)
        return props, valid

    hw = jnp.asarray([64.0, 64.0])
    props, valid = run(params, state, feats, hw)
    assert props.shape == (1, 8, 4)
    assert float(props.max()) <= 64.0
    # same result as the concrete-tuple path
    (props2, _), _ = rp.apply(params, state, feats, (64, 64))
    np.testing.assert_allclose(np.asarray(props), np.asarray(props2),
                               rtol=1e-6)


def test_proposal_traced_im_info_under_jit():
    prop = nn.Proposal(pre_nms_top_n=40, post_nms_top_n=6, scales=(8,),
                       min_size=4)
    params, state = prop.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    na = prop.anchor.num
    cls_prob = jnp.asarray(r.rand(1, 8, 8, 2 * na).astype(np.float32))
    bbox = jnp.asarray(0.1 * r.randn(1, 8, 8, 4 * na).astype(np.float32))

    @jax.jit
    def run(p, s, cp, bb, hw):
        (rois, valid), _ = prop.apply(p, s, cp, bb, hw)
        return rois, valid

    rois, valid = run(params, state, cls_prob, bbox,
                      jnp.asarray([128.0, 128.0]))
    assert rois.shape == (1, 6, 4)

    # identical to the static-clip path when both run under jit (eager vs
    # jit can differ by ulps and flip NMS near-ties, so compare jit-vs-jit)
    @jax.jit
    def run_static(p, s, cp, bb):
        (r2, v2), _ = prop.apply(p, s, cp, bb, (128, 128))
        return r2, v2

    rois2, _ = run_static(params, state, cls_prob, bbox)
    np.testing.assert_allclose(np.asarray(rois), np.asarray(rois2),
                               rtol=1e-6)
