"""Shared layer catalog: one construction recipe (builder + example inputs)
for EVERY public Module/Criterion in `bigdl_tpu.nn`.

This is the closure analogue of the reference's per-layer spec files
(reference: spark/dl/src/test/ — 374 layer specs + per-layer
ModuleSerializationTests): instead of 374 hand-written files, one catalog
drives three meta-suites:

  * tests/test_layer_closure.py   — asserts every public class is covered
  * tests/test_serializer_sweep2.py — durable-format round-trip per entry
  * tests/test_gradcheck2.py      — sampled numeric-vs-autodiff gradients

Entry conventions:
  build()   -> Module or Criterion instance
  inputs()  -> tuple of apply()/forward() positional inputs. For criterions:
               (input, target).
  grad      -> include in the numeric gradient sweep (False for selection /
               post-processing / host-side ops whose outputs are indices or
               whose gradients are intentionally non-standard).
  train_rng -> apply with training=True and a fixed rng (stochastic layers).
  post      -> map the raw output to comparable/differentiable arrays
               (e.g. SparseCOO.to_dense).
  host      -> the layer takes or makes a SparseCOO on the host (numpy) and
               cannot be traced: a sweep that jits its forwards runs these
               eagerly.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.sparse import SparseCOO


# --------------------------------------------------------------- input makers
def x(*s, seed=0, scale=1.0, offset=0.0):
    r = np.random.RandomState((abs(hash(s)) + seed) % (2 ** 31))
    return jnp.asarray((r.randn(*s) * scale + offset).astype(np.float32))


def away(*s, seed=0, gap=0.2):
    """Random values kept `gap` away from zero (kink-free numeric diffs)."""
    v = x(*s, seed=seed)
    return v + gap * jnp.sign(v)


def pos(*s, seed=0):
    return jnp.abs(x(*s, seed=seed)) + 0.3


def prob(*s, seed=0):
    return jax.nn.softmax(x(*s, seed=seed), axis=-1)


def logp(*s, seed=0):
    return jax.nn.log_softmax(x(*s, seed=seed), axis=-1)


def ints(hi, *s, seed=0):
    r = np.random.RandomState((abs(hash(s)) + seed + 7) % (2 ** 31))
    return jnp.asarray(r.randint(0, hi, s), jnp.int32)


def sgn(*s, seed=0):
    return jnp.sign(away(*s, seed=seed))


def binary(*s, seed=0):
    return (x(*s, seed=seed) > 0).astype(jnp.float32)


def sparse(b, n, k, seed=0):
    r = np.random.RandomState(seed + 11)
    dense = r.rand(b, n).astype(np.float32)
    dense[dense < 0.7] = 0.0
    return SparseCOO.from_dense(dense, nnz_per_row=k)


def _tree_3():
    """Two leaves + root, TensorTree layout [left, right, leaf] (1-based)."""
    t = np.zeros((2, 3, 3), np.int32)
    t[:, 0] = [0, 0, 1]
    t[:, 1] = [0, 0, 2]
    t[:, 2] = [1, 2, 0]
    return jnp.asarray(t)


class E:
    """One catalog entry."""

    def __init__(self, build, inputs, *, grad=True, ser=True,
                 train_rng=False, post=None, kwargs=None, host=False):
        self.build = build
        self.inputs = inputs
        self.grad = grad
        self.ser = ser
        self.train_rng = train_rng
        self.post = post
        self.kwargs = kwargs or {}
        self.host = host


_dense = lambda o: o.to_dense() if isinstance(o, SparseCOO) else o

# =========================================================== module catalog
MODULES = {
    # ---- elementwise activations
    "Abs": E(lambda: nn.Abs(), lambda: (away(3, 4),)),
    "BinaryThreshold": E(lambda: nn.BinaryThreshold(), lambda: (away(3, 4),)),
    "Clamp": E(lambda: nn.Clamp(-1.0, 1.0), lambda: (x(3, 4),)),
    "Clip": E(lambda: nn.Clip(-0.5, 0.5), lambda: (x(3, 4),)),
    "ELU": E(lambda: nn.ELU(0.7), lambda: (away(3, 4),)),
    "GELU": E(lambda: nn.GELU(), lambda: (x(3, 4),)),
    "HardShrink": E(lambda: nn.HardShrink(0.4), lambda: (x(3, 4),)),
    "HardSigmoid": E(lambda: nn.HardSigmoid(), lambda: (x(3, 4),)),
    "HardTanh": E(lambda: nn.HardTanh(-0.7, 0.7), lambda: (x(3, 4),)),
    "LeakyReLU": E(lambda: nn.LeakyReLU(0.2), lambda: (away(3, 4),)),
    "Log": E(lambda: nn.Log(), lambda: (pos(3, 4),)),
    "LogSigmoid": E(lambda: nn.LogSigmoid(), lambda: (x(3, 4),)),
    "LogSoftMax": E(lambda: nn.LogSoftMax(), lambda: (x(3, 5),)),
    "Exp": E(lambda: nn.Exp(), lambda: (x(3, 4),)),
    "Negative": E(lambda: nn.Negative(), lambda: (x(3, 4),)),
    "PReLU": E(lambda: nn.PReLU(3), lambda: (away(2, 4, 4, 3),)),
    "ReLU": E(lambda: nn.ReLU(), lambda: (away(3, 4),)),
    "ReLU6": E(lambda: nn.ReLU6(), lambda: (away(3, 4),)),
    "RReLU": E(lambda: nn.RReLU(), lambda: (away(3, 4),), train_rng=True),
    "SELU": E(lambda: nn.SELU(), lambda: (away(3, 4),)),
    "SReLU": E(lambda: nn.SReLU((4,)), lambda: (away(3, 4),)),
    "Sigmoid": E(lambda: nn.Sigmoid(), lambda: (x(3, 4),)),
    "SoftMax": E(lambda: nn.SoftMax(), lambda: (x(3, 5),)),
    "SoftMin": E(lambda: nn.SoftMin(), lambda: (x(3, 5),)),
    "SoftPlus": E(lambda: nn.SoftPlus(1.5), lambda: (x(3, 4),)),
    "SoftShrink": E(lambda: nn.SoftShrink(0.4), lambda: (x(3, 4),)),
    "SoftSign": E(lambda: nn.SoftSign(), lambda: (x(3, 4),)),
    "Sqrt": E(lambda: nn.Sqrt(), lambda: (pos(3, 4),)),
    "Square": E(lambda: nn.Square(), lambda: (x(3, 4),)),
    "Swish": E(lambda: nn.Swish(), lambda: (x(3, 4),)),
    "Tanh": E(lambda: nn.Tanh(), lambda: (x(3, 4),)),
    "TanhShrink": E(lambda: nn.TanhShrink(), lambda: (x(3, 4),)),
    "Threshold": E(lambda: nn.Threshold(0.0, -1.0), lambda: (away(3, 4),)),
    # ---- parametric linear family
    "Add": E(lambda: nn.Add(5), lambda: (x(3, 5),)),
    "Bilinear": E(lambda: nn.Bilinear(3, 4, 5),
                  lambda: (x(3, 3), x(3, 4))),
    "CAdd": E(lambda: nn.CAdd((1, 4)), lambda: (x(3, 4),)),
    "CMul": E(lambda: nn.CMul((1, 4)), lambda: (x(3, 4),)),
    "Cosine": E(lambda: nn.Cosine(4, 6), lambda: (x(3, 4),)),
    "Euclidean": E(lambda: nn.Euclidean(4, 6), lambda: (x(3, 4),)),
    "Linear": E(lambda: nn.Linear(6, 4), lambda: (x(3, 6),)),
    "Maxout": E(lambda: nn.Maxout(4, 3, 2), lambda: (x(3, 4),)),
    "Mul": E(lambda: nn.Mul(), lambda: (x(3, 4),)),
    "Highway": E(lambda: nn.Highway(5), lambda: (x(3, 5),)),
    # ---- embeddings / sparse
    "Embedding": E(lambda: nn.Embedding(11, 6),
                   lambda: (ints(11, 3, 4),)),
    "LookupTable": E(lambda: nn.LookupTable(11, 6),
                     lambda: (ints(11, 3, 4),)),
    "LookupTableSparse": E(lambda: nn.LookupTableSparse(16, 5),
                           lambda: (sparse(3, 16, 4),), host=True),
    "SparseLinear": E(lambda: nn.SparseLinear(16, 5),
                      lambda: (sparse(3, 16, 4),), host=True),
    "SparseJoinTable": E(lambda: nn.SparseJoinTable(),
                         lambda: (sparse(3, 8, 3), sparse(3, 6, 2, seed=1)),
                         grad=False, post=_dense, host=True),
    "DenseToSparse": E(lambda: nn.DenseToSparse(4),
                       lambda: (x(3, 8),), grad=False, post=_dense,
                       host=True),
    # ---- convolutions
    "SpatialConvolution": E(
        lambda: nn.SpatialConvolution(2, 3, 3, 3, pad_w=1, pad_h=1),
        lambda: (x(2, 6, 6, 2),)),
    "SpatialShareConvolution": E(
        lambda: nn.SpatialShareConvolution(2, 3, 3, 3),
        lambda: (x(1, 6, 6, 2),)),
    "SpatialDilatedConvolution": E(
        lambda: nn.SpatialDilatedConvolution(2, 3, 3, 3, dilation_w=2,
                                             dilation_h=2),
        lambda: (x(1, 8, 8, 2),)),
    "SpatialFullConvolution": E(
        lambda: nn.SpatialFullConvolution(2, 3, 3, 3, 2, 2),
        lambda: (x(1, 5, 5, 2),)),
    "SpatialSeparableConvolution": E(
        lambda: nn.SpatialSeparableConvolution(2, 4, 2, 3, 3),
        lambda: (x(1, 6, 6, 2),)),
    "SpatialConvolutionMap": E(
        lambda: nn.SpatialConvolutionMap([(0, 0), (1, 0), (1, 1)], 3, 3),
        lambda: (x(1, 6, 6, 2),)),
    "TemporalConvolution": E(lambda: nn.TemporalConvolution(3, 4, 3),
                             lambda: (x(2, 7, 3),)),
    "LocallyConnected1D": E(lambda: nn.LocallyConnected1D(6, 3, 4, 3),
                            lambda: (x(2, 6, 3),)),
    "LocallyConnected2D": E(
        lambda: nn.LocallyConnected2D(2, 5, 5, 3, 3, 3),
        lambda: (x(2, 5, 5, 2),)),
    "VolumetricConvolution": E(
        lambda: nn.VolumetricConvolution(2, 3, 2, 2, 2),
        lambda: (x(1, 4, 4, 4, 2),)),
    "VolumetricFullConvolution": E(
        lambda: nn.VolumetricFullConvolution(2, 3, 2, 2, 2, 2, 2, 2),
        lambda: (x(1, 3, 3, 3, 2),)),
    # ---- pooling
    "SpatialMaxPooling": E(lambda: nn.SpatialMaxPooling(2, 2),
                           lambda: (x(1, 5, 5, 2),)),
    "SpatialAveragePooling": E(lambda: nn.SpatialAveragePooling(2, 2),
                               lambda: (x(1, 5, 5, 2),)),
    "SpatialAdaptiveMaxPooling": E(
        lambda: nn.SpatialAdaptiveMaxPooling(2, 3),
        lambda: (x(1, 6, 6, 2),)),
    "GlobalAveragePooling2D": E(lambda: nn.GlobalAveragePooling2D(),
                                lambda: (x(2, 4, 4, 3),)),
    "TemporalMaxPooling": E(lambda: nn.TemporalMaxPooling(2),
                            lambda: (x(2, 6, 3),)),
    "TemporalAveragePooling": E(lambda: nn.TemporalAveragePooling(2),
                                lambda: (x(2, 6, 3),)),
    "VolumetricMaxPooling": E(lambda: nn.VolumetricMaxPooling(2, 2, 2),
                              lambda: (x(1, 4, 4, 4, 2),)),
    "VolumetricAveragePooling": E(lambda: nn.VolumetricAveragePooling(2, 2, 2),
                                  lambda: (x(1, 4, 4, 4, 2),)),
    # ---- normalization
    "BatchNormalization": E(lambda: nn.BatchNormalization(4),
                            lambda: (x(6, 4),)),
    "SpatialBatchNormalization": E(lambda: nn.SpatialBatchNormalization(3),
                                   lambda: (x(2, 4, 4, 3),)),
    "LayerNormalization": E(lambda: nn.LayerNormalization(5),
                            lambda: (x(3, 5),)),
    "RMSNorm": E(lambda: nn.RMSNorm(5), lambda: (x(3, 5),)),
    "Normalize": E(lambda: nn.Normalize(2.0), lambda: (x(3, 5),)),
    "NormalizeScale": E(lambda: nn.NormalizeScale(2.0, 20.0, (1, 1, 1, 4)),
                        lambda: (x(2, 3, 3, 4),)),
    "SpatialCrossMapLRN": E(lambda: nn.SpatialCrossMapLRN(3),
                            lambda: (x(1, 4, 4, 6),)),
    "SpatialWithinChannelLRN": E(lambda: nn.SpatialWithinChannelLRN(3),
                                 lambda: (x(1, 5, 5, 2),)),
    "SpatialSubtractiveNormalization": E(
        lambda: nn.SpatialSubtractiveNormalization(2),
        lambda: (x(1, 6, 6, 2),)),
    "SpatialDivisiveNormalization": E(
        lambda: nn.SpatialDivisiveNormalization(2),
        lambda: (x(1, 6, 6, 2),)),
    "SpatialContrastiveNormalization": E(
        lambda: nn.SpatialContrastiveNormalization(2),
        lambda: (x(1, 6, 6, 2),)),
    # ---- dropout family (training mode, fixed rng)
    "Dropout": E(lambda: nn.Dropout(0.4), lambda: (x(3, 5),),
                 train_rng=True),
    "GaussianDropout": E(lambda: nn.GaussianDropout(0.3),
                         lambda: (x(3, 5),), train_rng=True),
    "GaussianNoise": E(lambda: nn.GaussianNoise(0.2), lambda: (x(3, 5),),
                       train_rng=True),
    "SpatialDropout1D": E(lambda: nn.SpatialDropout1D(0.4),
                          lambda: (x(2, 5, 3),), train_rng=True),
    "SpatialDropout2D": E(lambda: nn.SpatialDropout2D(0.4),
                          lambda: (x(2, 4, 4, 3),), train_rng=True),
    "SpatialDropout3D": E(lambda: nn.SpatialDropout3D(0.4),
                          lambda: (x(1, 3, 3, 3, 2),), train_rng=True),
    "GaussianSampler": E(lambda: nn.GaussianSampler(),
                         lambda: ((x(3, 4), x(3, 4, seed=1)),),
                         train_rng=True),
    # ---- shape ops
    "Contiguous": E(lambda: nn.Contiguous(), lambda: (x(3, 4),)),
    "Echo": E(lambda: nn.Echo(), lambda: (x(3, 4),)),
    "Flatten": E(lambda: nn.Flatten(), lambda: (x(2, 3, 4),)),
    "FlattenTable": E(lambda: nn.FlattenTable(),
                      lambda: ((x(2, 3), (x(2, 3, seed=1),
                                          x(2, 3, seed=2))),)),
    "Identity": E(lambda: nn.Identity(), lambda: (x(3, 4),)),
    "Index": E(lambda: nn.Index(0), lambda: (x(5, 4), ints(5, 3))),
    "Gather": E(lambda: nn.Gather(0), lambda: (x(5, 4), ints(5, 3))),
    "InferReshape": E(lambda: nn.InferReshape((-1, 6)),
                      lambda: (x(4, 3, 2),)),
    "JoinTable": E(lambda: nn.JoinTable(1),
                   lambda: (x(2, 3), x(2, 4))),
    "Masking": E(lambda: nn.Masking(0.0), lambda: (away(2, 4, 3),)),
    "Narrow": E(lambda: nn.Narrow(1, 1, 2), lambda: (x(3, 5),)),
    "Padding": E(lambda: nn.Padding(1, 2, value=0.5), lambda: (x(3, 4),)),
    "Permute": E(lambda: nn.Permute((1, 0)), lambda: (x(2, 3, 4),)),
    "Replicate": E(lambda: nn.Replicate(3, 1), lambda: (x(2, 4),)),
    "Reshape": E(lambda: nn.Reshape((2, 6)), lambda: (x(3, 3, 4),)),
    "ResizeBilinear": E(lambda: nn.ResizeBilinear(6, 8),
                        lambda: (x(1, 4, 5, 2),)),
    "Reverse": E(lambda: nn.Reverse(1), lambda: (x(3, 4),)),
    "Select": E(lambda: nn.Select(1, 2), lambda: (x(3, 5),)),
    "SelectTable": E(lambda: nn.SelectTable(1),
                     lambda: (x(2, 3), x(2, 4))),
    "SpatialZeroPadding": E(lambda: nn.SpatialZeroPadding(1, 2, 1, 0),
                            lambda: (x(1, 4, 4, 2),)),
    "SplitTable": E(lambda: nn.SplitTable(1), lambda: (x(3, 4),)),
    "Squeeze": E(lambda: nn.Squeeze(1), lambda: (x(3, 1, 4),)),
    "Tile": E(lambda: nn.Tile(1, 3), lambda: (x(2, 3),)),
    "Transpose": E(lambda: nn.Transpose(((1, 2),)), lambda: (x(2, 3, 4),)),
    "Unsqueeze": E(lambda: nn.Unsqueeze(1), lambda: (x(3, 4),)),
    "UpSampling1D": E(lambda: nn.UpSampling1D(2), lambda: (x(2, 4, 3),)),
    "UpSampling2D": E(lambda: nn.UpSampling2D((2, 2)),
                      lambda: (x(1, 3, 3, 2),)),
    "UpSampling3D": E(lambda: nn.UpSampling3D((2, 2, 2)),
                      lambda: (x(1, 3, 3, 3, 2),)),
    "View": E(lambda: nn.View((12,)), lambda: (x(2, 3, 4),)),
    "ExpandSize": E(lambda: nn.ExpandSize((3, 4)), lambda: (x(1, 4),)),
    "Pack": E(lambda: nn.Pack(1), lambda: (x(2, 3), x(2, 3, seed=1))),
    "NarrowTable": E(lambda: nn.NarrowTable(1, 2),
                     lambda: (x(2, 3), x(2, 3, seed=1), x(2, 3, seed=2))),
    "BifurcateSplitTable": E(lambda: nn.BifurcateSplitTable(1),
                             lambda: (x(3, 6),)),
    "Cropping2D": E(lambda: nn.Cropping2D((1, 1), (0, 1)),
                    lambda: (x(1, 5, 5, 2),)),
    "Cropping3D": E(lambda: nn.Cropping3D((1, 0), (0, 1), (1, 1)),
                    lambda: (x(1, 4, 4, 4, 2),)),
    "MaskedSelect": E(lambda: nn.MaskedSelect(8),
                      lambda: (x(3, 4), ints(2, 3, 4))),
    # ---- arithmetic / table math
    "AddConstant": E(lambda: nn.AddConstant(2.5), lambda: (x(3, 4),)),
    "MulConstant": E(lambda: nn.MulConstant(1.7), lambda: (x(3, 4),)),
    "Power": E(lambda: nn.Power(2.5, scale=1.2, shift=0.1),
               lambda: (pos(3, 4),)),
    "CAddTable": E(lambda: nn.CAddTable(),
                   lambda: (x(3, 4), x(3, 4, seed=1))),
    "CSubTable": E(lambda: nn.CSubTable(),
                   lambda: (x(3, 4), x(3, 4, seed=1))),
    "CMulTable": E(lambda: nn.CMulTable(),
                   lambda: (x(3, 4), x(3, 4, seed=1))),
    "CDivTable": E(lambda: nn.CDivTable(),
                   lambda: (x(3, 4), pos(3, 4, seed=1))),
    "CMaxTable": E(lambda: nn.CMaxTable(),
                   lambda: (x(3, 4), x(3, 4, seed=1))),
    "CMinTable": E(lambda: nn.CMinTable(),
                   lambda: (x(3, 4), x(3, 4, seed=1))),
    "CAveTable": E(lambda: nn.CAveTable(),
                   lambda: (x(3, 4), x(3, 4, seed=1))),
    "CosineDistance": E(lambda: nn.CosineDistance(),
                        lambda: (x(3, 4), x(3, 4, seed=1))),
    "CrossProduct": E(lambda: nn.CrossProduct(),
                      lambda: (x(2, 4), x(2, 4, seed=1), x(2, 4, seed=2))),
    "DotProduct": E(lambda: nn.DotProduct(),
                    lambda: (x(3, 4), x(3, 4, seed=1))),
    "PairwiseDistance": E(lambda: nn.PairwiseDistance(),
                          lambda: (x(3, 4), x(3, 4, seed=1))),
    "MM": E(lambda: nn.MM(), lambda: (x(2, 3, 4), x(2, 4, 5))),
    "MV": E(lambda: nn.MV(), lambda: (x(2, 3, 4), x(2, 4))),
    "Max": E(lambda: nn.Max(1), lambda: (x(3, 5),)),
    "Min": E(lambda: nn.Min(1), lambda: (x(3, 5),)),
    "Mean": E(lambda: nn.Mean(1), lambda: (x(3, 5),)),
    "Sum": E(lambda: nn.Sum(1), lambda: (x(3, 5),)),
    "MixtureTable": E(lambda: nn.MixtureTable(),
                      lambda: (prob(2, 3), x(2, 3, 5))),
    "Scale": E(lambda: nn.Scale((1, 4)), lambda: (x(3, 4),)),
    "TableOperation": E(lambda: nn.TableOperation(nn.CMulTable()),
                        lambda: (x(2, 3, 4), x(2, 3))),
    # ---- penalties / misc identity-with-aux
    "ActivityRegularization": E(lambda: nn.ActivityRegularization(0.1, 0.2),
                                lambda: (x(3, 4),)),
    "L1Penalty": E(lambda: nn.L1Penalty(0.5), lambda: (away(3, 4),)),
    "NegativeEntropyPenalty": E(lambda: nn.NegativeEntropyPenalty(),
                                lambda: (prob(3, 4),)),
    "GradientReversal": E(lambda: nn.GradientReversal(0.7),
                          lambda: (x(3, 4),), grad=False),
    # ---- containers
    "Sequential": E(lambda: nn.Sequential(nn.Linear(4, 5), nn.ReLU(),
                                          nn.Linear(5, 3)),
                    lambda: (x(2, 4),)),
    "Concat": E(lambda: nn.Concat(nn.Linear(4, 3), nn.Linear(4, 2),
                                  axis=-1),
                lambda: (x(2, 4),)),
    "ConcatTable": E(lambda: nn.ConcatTable(nn.Linear(4, 3), nn.Tanh()),
                     lambda: (x(2, 4),)),
    "ParallelTable": E(lambda: nn.ParallelTable(nn.Linear(4, 3),
                                                nn.Tanh()),
                       lambda: (x(2, 4), x(2, 3, seed=1))),
    "Bottle": E(lambda: nn.Bottle(nn.Linear(4, 3), 2),
                lambda: (x(2, 3, 4),)),
    "MapTable": E(lambda: nn.MapTable(nn.Linear(4, 3)),
                  lambda: (x(2, 4), x(2, 4, seed=1))),
    "Graph": E(lambda: _small_graph(), lambda: (x(2, 6),)),
    # ---- recurrent stack
    "Recurrent": E(lambda: nn.Recurrent(nn.LSTM(4, 5)),
                   lambda: (x(2, 4, 4),)),
    "LSTMPeephole": E(lambda: nn.Recurrent(nn.LSTMPeephole(4, 5)),
                      lambda: (x(2, 4, 4),)),
    "GRU": E(lambda: nn.Recurrent(nn.GRU(4, 5)), lambda: (x(2, 4, 4),)),
    "RnnCell": E(lambda: nn.Recurrent(nn.RnnCell(4, 5)),
                 lambda: (x(2, 4, 4),)),
    "MultiRNNCell": E(
        lambda: nn.Recurrent(nn.MultiRNNCell([nn.RnnCell(4, 4),
                                              nn.RnnCell(4, 4)])),
        lambda: (x(2, 4, 4),)),
    "ConvLSTMPeephole": E(
        lambda: nn.Recurrent(nn.ConvLSTMPeephole(2, 3, 3, (4, 4))),
        lambda: (x(1, 3, 4, 4, 2),)),
    "ConvLSTMPeephole3D": E(
        lambda: nn.Recurrent(nn.ConvLSTMPeephole3D(1, 2, 3, (3, 3, 3))),
        lambda: (x(1, 2, 3, 3, 3, 1),)),
    "BiRecurrent": E(lambda: nn.BiRecurrent(nn.GRU(4, 5), nn.GRU(4, 5)),
                     lambda: (x(2, 4, 4),)),
    "RecurrentDecoder": E(lambda: nn.RecurrentDecoder(nn.RnnCell(4, 4), 3),
                          lambda: (x(2, 4),)),
    "TimeDistributed": E(lambda: nn.TimeDistributed(nn.Linear(4, 3)),
                         lambda: (x(2, 5, 4),)),
    "BinaryTreeLSTM": E(lambda: nn.BinaryTreeLSTM(4, 5),
                        lambda: (x(2, 2, 4), _tree_3())),
    # ---- attention / transformer
    "MultiHeadAttention": E(lambda: nn.MultiHeadAttention(8, 2),
                            lambda: (x(1, 5, 8),)),
    "Attention": E(lambda: nn.Attention(8, 2), lambda: (x(1, 5, 8),)),
    "FeedForwardNetwork": E(lambda: nn.FeedForwardNetwork(8, 16),
                            lambda: (x(1, 5, 8),)),
    "TransformerLayer": E(lambda: nn.TransformerLayer(8, 2, 16),
                          lambda: (x(1, 5, 8),)),
    "Transformer": E(
        lambda: nn.Transformer(11, 8, 2, 16, 2, max_len=8),
        lambda: (ints(11, 1, 5),)),
    # ---- detection / rcnn
    "Nms": E(lambda: nn.Nms(0.5, 4),
             lambda: (pos(6, 4) * 20.0, pos(6)), grad=False),
    "RoiAlign": E(lambda: nn.RoiAlign((2, 2), spatial_scale=0.5),
                  lambda: (x(1, 8, 8, 3),
                           jnp.asarray([[0, 0, 8, 8], [2, 2, 12, 12]],
                                       jnp.float32),
                           jnp.zeros((2,), jnp.int32))),
    "RoiPooling": E(lambda: nn.RoiPooling(2, 2, spatial_scale=0.5),
                    lambda: (x(1, 8, 8, 3),
                             jnp.asarray([[0, 0, 8, 8]], jnp.float32),
                             jnp.zeros((1,), jnp.int32))),
    "Pooler": E(lambda: nn.Pooler((2, 2), scales=(0.25, 0.125),
                                  canonical_size=32.0),
                lambda: ((x(1, 8, 8, 2), x(1, 4, 4, 2)),
                         jnp.asarray([[0, 0, 16, 16], [0, 0, 30, 30]],
                                     jnp.float32),
                         jnp.zeros((2,), jnp.int32))),
    "FPN": E(lambda: nn.FPN([4, 6], 3),
             lambda: ((x(1, 8, 8, 4), x(1, 4, 4, 6)),)),
    # NMS selection can flip under finite-difference perturbation (like a
    # tied maxpool) — numeric gradcheck is unstable; numpy-pipeline golden
    # in test_golden_oracle.py instead
    "DetectionOutputSSD": E(
        lambda: nn.DetectionOutputSSD(n_classes=3, top_k=4),
        lambda: (pos(5, 4) * 10.0, x(5, 4, scale=0.1), prob(5, 3)),
        grad=False),
    "DetectionOutputFrcnn": E(
        lambda: nn.DetectionOutputFrcnn(n_classes=4, max_per_image=6),
        lambda: (prob(5, 4), x(5, 16, scale=0.1), pos(5, 4) * 20.0),
        grad=True),
    "Proposal": E(
        lambda: nn.Proposal(pre_nms_top_n=40, post_nms_top_n=6,
                            scales=(8,), min_size=4),
        lambda: (prob(1, 8, 8, 6), x(1, 8, 8, 12, scale=0.1),
                 jnp.asarray([64.0, 64.0])),
        grad=True),
    # same NMS-flip instability; numpy-pipeline golden in
    # test_golden_oracle.py instead
    "RegionProposal": E(
        lambda: nn.RegionProposal(in_channels=4, anchor_sizes=(16,),
                                  anchor_stride=(8,), pre_nms_top_n=20,
                                  post_nms_top_n=8),
        lambda: ((x(1, 8, 8, 4),), (64, 64)), grad=False),
    "BoxHead": E(
        lambda: nn.BoxHead(in_channels=4, resolution=4, scales=(0.25,),
                           sampling_ratio=2, score_thresh=0.0,
                           nms_thresh=0.5, max_per_image=4, output_size=16,
                           num_classes=3),
        lambda: ([x(1, 16, 16, 4)],
                 jnp.asarray([[0, 0, 32, 32], [8, 8, 56, 56]], jnp.float32),
                 (64, 64)),
        grad=True),
    "MaskHead": E(
        lambda: nn.MaskHead(in_channels=4, resolution=4, scales=(0.25,),
                            sampling_ratio=2, layers=(8,), dilation=1,
                            num_classes=3),
        lambda: ([x(1, 16, 16, 4)],
                 jnp.asarray([[0, 0, 32, 32]], jnp.float32),
                 jnp.asarray([1], jnp.int32)),
        grad=True),
}


def _small_graph():
    from bigdl_tpu.core.container import Graph, Input
    inp = Input()
    a = nn.Linear(6, 5)(inp)
    b = nn.ReLU()(a)
    c = nn.Linear(6, 5)(inp)
    d = nn.CAddTable()(b, c)
    return Graph([inp], [nn.Linear(5, 3)(d)])


# ======================================================== criterion catalog
def _mc():
    m = nn.MultiCriterion()
    m.add(nn.MSECriterion()).add(nn.AbsCriterion(), 0.5)
    return m


def _pc():
    p = nn.ParallelCriterion()
    p.add(nn.MSECriterion()).add(nn.ClassNLLCriterion(), 0.5)
    return p


CRITERIA = {
    "AbsCriterion": E(lambda: nn.AbsCriterion(),
                      lambda: (x(3, 4), x(3, 4, seed=1))),
    "MSECriterion": E(lambda: nn.MSECriterion(),
                      lambda: (x(3, 4), x(3, 4, seed=1))),
    "SmoothL1Criterion": E(lambda: nn.SmoothL1Criterion(),
                           lambda: (x(3, 4), x(3, 4, seed=1))),
    "SmoothL1CriterionWithWeights": E(
        lambda: nn.SmoothL1CriterionWithWeights(2.0, 3),
        lambda: (x(3, 4), (x(3, 4, seed=1), pos(3, 4), pos(3, 4, seed=2)))),
    "BCECriterion": E(lambda: nn.BCECriterion(),
                      lambda: (jax.nn.sigmoid(x(3, 4)), binary(3, 4))),
    "BCECriterionWithLogits": E(lambda: nn.BCECriterionWithLogits(),
                                lambda: (x(3, 4), binary(3, 4))),
    "ClassNLLCriterion": E(lambda: nn.ClassNLLCriterion(),
                           lambda: (logp(3, 5), ints(5, 3))),
    "CrossEntropyCriterion": E(lambda: nn.CrossEntropyCriterion(),
                               lambda: (x(3, 5), ints(5, 3))),
    "CategoricalCrossEntropy": E(
        lambda: nn.CategoricalCrossEntropy(),
        lambda: (prob(3, 5), jax.nn.one_hot(ints(5, 3), 5))),
    "ClassSimplexCriterion": E(lambda: nn.ClassSimplexCriterion(5),
                               lambda: (x(3, 5), ints(5, 3))),
    "CosineDistanceCriterion": E(lambda: nn.CosineDistanceCriterion(),
                                 lambda: (x(3, 4), x(3, 4, seed=1))),
    "CosineEmbeddingCriterion": E(
        lambda: nn.CosineEmbeddingCriterion(0.2),
        lambda: ((x(3, 4), x(3, 4, seed=1)), sgn(3))),
    "CosineProximityCriterion": E(lambda: nn.CosineProximityCriterion(),
                                  lambda: (x(3, 4), x(3, 4, seed=1))),
    "DiceCoefficientCriterion": E(lambda: nn.DiceCoefficientCriterion(),
                                  lambda: (prob(3, 4), binary(3, 4))),
    "DistKLDivCriterion": E(lambda: nn.DistKLDivCriterion(),
                            lambda: (logp(3, 5), prob(3, 5, seed=1))),
    "KLDivCriterion": E(lambda: nn.KLDivCriterion(),
                        lambda: (logp(3, 5), prob(3, 5, seed=1))),
    "KullbackLeiblerDivergenceCriterion": E(
        lambda: nn.KullbackLeiblerDivergenceCriterion(),
        lambda: (prob(3, 5), prob(3, 5, seed=1))),
    "DotProductCriterion": E(lambda: nn.DotProductCriterion(),
                             lambda: (x(3, 4), x(3, 4, seed=1))),
    "GaussianCriterion": E(lambda: nn.GaussianCriterion(),
                           lambda: ((x(3, 4), x(3, 4, seed=1)),
                                    x(3, 4, seed=2))),
    "KLDCriterion": E(lambda: nn.KLDCriterion(),
                      lambda: ((x(3, 4), x(3, 4, seed=1)),
                               jnp.zeros((3, 4)))),
    "HingeEmbeddingCriterion": E(lambda: nn.HingeEmbeddingCriterion(),
                                 lambda: (pos(6), sgn(6))),
    "L1Cost": E(lambda: nn.L1Cost(), lambda: (away(3, 4), None)),
    "L1HingeEmbeddingCriterion": E(
        lambda: nn.L1HingeEmbeddingCriterion(0.8),
        lambda: ((x(3, 4), x(3, 4, seed=1)), sgn(3))),
    "MarginCriterion": E(lambda: nn.MarginCriterion(),
                         lambda: (x(3, 4), sgn(3, 4))),
    "MarginRankingCriterion": E(lambda: nn.MarginRankingCriterion(),
                                lambda: ((x(5), x(5, seed=1)), sgn(5))),
    "MeanAbsolutePercentageCriterion": E(
        lambda: nn.MeanAbsolutePercentageCriterion(),
        lambda: (x(3, 4), pos(3, 4))),
    "MeanSquaredLogarithmicCriterion": E(
        lambda: nn.MeanSquaredLogarithmicCriterion(),
        lambda: (pos(3, 4), pos(3, 4, seed=1))),
    "MultiCriterion": E(_mc, lambda: (x(3, 4), x(3, 4, seed=1))),
    "ParallelCriterion": E(
        _pc, lambda: ((x(3, 4), logp(3, 5)),
                      (x(3, 4, seed=1), ints(5, 3)))),
    "MultiLabelMarginCriterion": E(lambda: nn.MultiLabelMarginCriterion(),
                                   lambda: (x(3, 5), binary(3, 5))),
    "MultiLabelSoftMarginCriterion": E(
        lambda: nn.MultiLabelSoftMarginCriterion(),
        lambda: (x(3, 5), binary(3, 5))),
    "MultiMarginCriterion": E(lambda: nn.MultiMarginCriterion(),
                              lambda: (x(3, 5), ints(5, 3))),
    "PGCriterion": E(lambda: nn.PGCriterion(),
                     lambda: (logp(3, 5), (ints(5, 3), x(3)))),
    "PoissonCriterion": E(lambda: nn.PoissonCriterion(),
                          lambda: (pos(3, 4), pos(3, 4, seed=1))),
    "SoftMarginCriterion": E(lambda: nn.SoftMarginCriterion(),
                             lambda: (x(3, 4), sgn(3, 4))),
    "SoftmaxWithCriterion": E(lambda: nn.SoftmaxWithCriterion(),
                              lambda: (x(2, 3, 3, 5), ints(5, 2, 3, 3))),
    "TimeDistributedCriterion": E(
        lambda: nn.TimeDistributedCriterion(nn.ClassNLLCriterion()),
        lambda: (logp(2, 4, 5), ints(5, 2, 4))),
    "TimeDistributedMaskCriterion": E(
        lambda: nn.TimeDistributedMaskCriterion(nn.ClassNLLCriterion(),
                                                padding_value=0),
        lambda: (logp(2, 4, 5), ints(5, 2, 4))),
    "TransformerCriterion": E(
        lambda: nn.TransformerCriterion(nn.MSECriterion()),
        lambda: (x(3, 4), x(3, 4, seed=1))),
    "DistKLDivCriterion_alias": E(lambda: nn.KLDivCriterion(),
                                  lambda: (logp(3, 5), prob(3, 5, seed=1)),
                                  ser=False, grad=False),
}

# Abstract bases and classes whose construction needs task-specific
# closures; each is covered elsewhere (see test_layer_closure.py).
EXEMPT = {
    "Module", "Criterion", "Container", "Cell", "TreeLSTM",
    # step_fn closure is model-specific; beam search itself is
    # golden-tested token-for-token vs transformers' generate()
    # (tests/test_huggingface.py) and vs full forward (test_recurrent.py)
    "SequenceBeamSearch",
}


def covered_class_names():
    """Every Module/Criterion class name reachable from catalog entries."""
    names = set()
    for entry in MODULES.values():
        mod = entry.build()
        for m in mod.modules():
            names.add(type(m).__name__)
    for cname, entry in CRITERIA.items():
        crit = entry.build()
        stack = [crit]
        while stack:
            c = stack.pop()
            names.add(type(c).__name__)
            for attr in ("criterion",):
                inner = getattr(c, attr, None)
                if inner is not None:
                    stack.append(inner)
            stack.extend(getattr(c, "criterions", []) or [])
    return names
