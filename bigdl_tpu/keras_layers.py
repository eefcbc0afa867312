"""Keras-style layer constructors with input-shape inference
(reference: nn/keras/*.scala — ~60 KerasLayer classes whose
`computeOutputShape`/`doBuild` infer every dimension from the input shape;
pyspark/bigdl/nn/keras/layer.py mirrors them in Python).

Layers here are declarative configs; `Sequential.build()` runs them through
the same builder table the HDF5/JSON importer uses
(`interop/keras_loader._BUILDERS`), so `Dense(64)` after a `Conv2D` never
needs its input dim spelled out — the round-1 facade required explicit dims
everywhere.

    from bigdl_tpu import keras_layers as kl
    model = kl.Sequential(
        kl.Conv2D(32, (3, 3), activation="relu", padding="same",
                  input_shape=(32, 32, 3)),
        kl.MaxPooling2D(2),
        kl.Flatten(),
        kl.Dense(10, activation="softmax"),
    )
    model.compile("adam", "sparse_categorical_crossentropy", ["acc"])
    model.fit(x, y, batch_size=64, nb_epoch=5)

The result IS a `bigdl_tpu` module tree — `model.module`, `model.params`
compose with the trainer, quantization, serializer, and mesh optimizers.
"""

from __future__ import annotations

import itertools

import jax

from bigdl_tpu.keras import KerasModel

_name_counter = itertools.count()


class Layer(dict):
    """A layer config. Usable two ways, like keras:

      * appended to `Sequential` (it IS the config dict), or
      * called on symbolic tensors for the functional API:
        ``h = Dense(64, activation="relu")(x)`` (reference:
        nn/keras/KerasLayer.scala `inputs(...)` wiring).
    """

    def __call__(self, *inputs: "KTensor") -> "KTensor":
        if getattr(self, "_invoked", False):
            raise NotImplementedError(
                f"layer {self['config'].get('name')!r} called twice — "
                f"weight sharing across call sites is not supported")
        self._invoked = True
        self["config"].setdefault(
            "name",
            f"{self['class_name'].lower()}_{next(_name_counter)}")
        return KTensor(self, inputs)


class KTensor:
    """Symbolic output of a layer call (functional API handle)."""

    def __init__(self, layer: Layer, inputs):
        self.layer = layer
        self.inputs = tuple(inputs)

    @property
    def name(self) -> str:
        return self.layer["config"]["name"]


def Input(shape, name=None) -> KTensor:
    """Functional-API entry point (reference: nn/keras/Input.scala)."""
    cfg = Layer({"class_name": "InputLayer",
                 "config": {"batch_input_shape": [None] + list(shape)}})
    if name is not None:
        cfg["config"]["name"] = name
    return cfg()


def _cfg(class_name: str, input_shape=None, name=None, **kw) -> Layer:
    cfg = {k: v for k, v in kw.items() if v is not None}
    if input_shape is not None:
        cfg["batch_input_shape"] = [None] + list(input_shape)
    if name is not None:
        cfg["name"] = name
    return Layer({"class_name": class_name, "config": cfg})


def _pair(v):
    return list(v) if isinstance(v, (tuple, list)) else [v, v]


# ------------------------------------------------------------------- core
def Dense(units, activation=None, use_bias=True, input_shape=None,
          name=None):
    return _cfg("Dense", input_shape, name, units=units,
                activation=activation, use_bias=use_bias)


def Activation(activation, input_shape=None, name=None):
    return _cfg("Activation", input_shape, name, activation=activation)


def Dropout(rate, input_shape=None, name=None):
    return _cfg("Dropout", input_shape, name, rate=rate)


def Flatten(input_shape=None, name=None):
    return _cfg("Flatten", input_shape, name)


def Reshape(target_shape, input_shape=None, name=None):
    return _cfg("Reshape", input_shape, name,
                target_shape=list(target_shape))


def Permute(dims, input_shape=None, name=None):
    return _cfg("Permute", input_shape, name, dims=list(dims))


def RepeatVector(n, input_shape=None, name=None):
    return _cfg("RepeatVector", input_shape, name, n=n)


def Masking(mask_value=0.0, input_shape=None, name=None):
    return _cfg("Masking", input_shape, name, mask_value=mask_value)


# ------------------------------------------------------------ convolution
def Conv2D(filters, kernel_size, strides=1, padding="valid",
           dilation_rate=1, groups=1, activation=None, use_bias=True,
           input_shape=None, name=None):
    return _cfg("Conv2D", input_shape, name, filters=filters,
                kernel_size=_pair(kernel_size), strides=_pair(strides),
                padding=padding, dilation_rate=_pair(dilation_rate),
                groups=groups, activation=activation, use_bias=use_bias)


def DepthwiseConv2D(kernel_size, strides=1, padding="valid",
                    depth_multiplier=1, activation=None, use_bias=True,
                    input_shape=None, name=None):
    return _cfg("DepthwiseConv2D", input_shape, name,
                kernel_size=_pair(kernel_size), strides=_pair(strides),
                padding=padding, depth_multiplier=depth_multiplier,
                activation=activation, use_bias=use_bias)


def SeparableConv2D(filters, kernel_size, strides=1, padding="valid",
                    depth_multiplier=1, activation=None, use_bias=True,
                    input_shape=None, name=None):
    return _cfg("SeparableConv2D", input_shape, name, filters=filters,
                kernel_size=_pair(kernel_size), strides=_pair(strides),
                padding=padding, depth_multiplier=depth_multiplier,
                activation=activation, use_bias=use_bias)


def Conv2DTranspose(filters, kernel_size, strides=1, padding="valid",
                    activation=None, use_bias=True, input_shape=None,
                    name=None):
    return _cfg("Conv2DTranspose", input_shape, name, filters=filters,
                kernel_size=_pair(kernel_size), strides=_pair(strides),
                padding=padding, activation=activation, use_bias=use_bias)


def Conv1D(filters, kernel_size, strides=1, padding="valid",
           activation=None, use_bias=True, input_shape=None, name=None):
    ks = kernel_size if isinstance(kernel_size, (tuple, list)) \
        else [kernel_size]
    st = strides if isinstance(strides, (tuple, list)) else [strides]
    return _cfg("Conv1D", input_shape, name, filters=filters,
                kernel_size=list(ks), strides=list(st), padding=padding,
                activation=activation, use_bias=use_bias)


def ZeroPadding2D(padding=1, input_shape=None, name=None):
    return _cfg("ZeroPadding2D", input_shape, name, padding=padding)


def UpSampling2D(size=2, input_shape=None, name=None):
    return _cfg("UpSampling2D", input_shape, name, size=_pair(size))


# ---------------------------------------------------------------- pooling
def MaxPooling2D(pool_size=2, strides=None, padding="valid",
                 input_shape=None, name=None):
    return _cfg("MaxPooling2D", input_shape, name,
                pool_size=_pair(pool_size),
                strides=None if strides is None else _pair(strides),
                padding=padding)


def AveragePooling2D(pool_size=2, strides=None, padding="valid",
                     input_shape=None, name=None):
    return _cfg("AveragePooling2D", input_shape, name,
                pool_size=_pair(pool_size),
                strides=None if strides is None else _pair(strides),
                padding=padding)


def MaxPooling1D(pool_size=2, strides=None, input_shape=None, name=None):
    return _cfg("MaxPooling1D", input_shape, name, pool_size=pool_size,
                strides=strides)


def GlobalAveragePooling2D(input_shape=None, name=None):
    return _cfg("GlobalAveragePooling2D", input_shape, name)


def GlobalMaxPooling2D(input_shape=None, name=None):
    return _cfg("GlobalMaxPooling2D", input_shape, name)


def GlobalAveragePooling1D(input_shape=None, name=None):
    return _cfg("GlobalAveragePooling1D", input_shape, name)


def GlobalMaxPooling1D(input_shape=None, name=None):
    return _cfg("GlobalMaxPooling1D", input_shape, name)


# ---------------------------------------------------------- normalization
def BatchNormalization(momentum=0.99, epsilon=1e-3, center=True, scale=True,
                       input_shape=None, name=None):
    return _cfg("BatchNormalization", input_shape, name, momentum=momentum,
                epsilon=epsilon, center=center, scale=scale)


def LayerNormalization(epsilon=1e-3, input_shape=None, name=None):
    return _cfg("LayerNormalization", input_shape, name, epsilon=epsilon)


# -------------------------------------------------------------- embedding
def Embedding(input_dim, output_dim, input_shape=None, name=None):
    return _cfg("Embedding", input_shape, name, input_dim=input_dim,
                output_dim=output_dim)


# -------------------------------------------------------------- recurrent
def LSTM(units, return_sequences=False, go_backwards=False,
         input_shape=None, name=None):
    return _cfg("LSTM", input_shape, name, units=units,
                return_sequences=return_sequences,
                go_backwards=go_backwards)


def GRU(units, return_sequences=False, go_backwards=False,
        reset_after=False, input_shape=None, name=None):
    return _cfg("GRU", input_shape, name, units=units,
                return_sequences=return_sequences,
                go_backwards=go_backwards, reset_after=reset_after)


def SimpleRNN(units, return_sequences=False, go_backwards=False,
              input_shape=None, name=None):
    return _cfg("SimpleRNN", input_shape, name, units=units,
                return_sequences=return_sequences,
                go_backwards=go_backwards)


def Bidirectional(layer, merge_mode="concat", input_shape=None, name=None):
    return _cfg("Bidirectional", input_shape, name, layer=layer,
                merge_mode=merge_mode)


def TimeDistributed(layer, input_shape=None, name=None):
    return _cfg("TimeDistributed", input_shape, name, layer=layer)


# ------------------------------------------------------- keras-1 layers
def Highway(activation="linear", input_shape=None, name=None):
    return _cfg("Highway", input_shape, name, activation=activation)


def MaxoutDense(output_dim, nb_feature=4, input_shape=None, name=None):
    return _cfg("MaxoutDense", input_shape, name, output_dim=output_dim,
                nb_feature=nb_feature)


def SReLU(shared_axes=None, input_shape=None, name=None):
    return _cfg("SReLU", input_shape, name, shared_axes=shared_axes)


# ----------------------------------------------------------------- merges
def Concatenate(axis=-1, name=None):
    return _cfg("Concatenate", None, name, axis=axis)


def Add(name=None):
    return _cfg("Add", None, name)


def Multiply(name=None):
    return _cfg("Multiply", None, name)


def Average(name=None):
    return _cfg("Average", None, name)


def Subtract(name=None):
    return _cfg("Subtract", None, name)


def Maximum(name=None):
    return _cfg("Maximum", None, name)


def Minimum(name=None):
    return _cfg("Minimum", None, name)


# ------------------------------------------------------------ activations
def LeakyReLU(alpha=0.3, input_shape=None, name=None):
    return _cfg("LeakyReLU", input_shape, name, alpha=alpha)


def ELU(alpha=1.0, input_shape=None, name=None):
    return _cfg("ELU", input_shape, name, alpha=alpha)


def PReLU(shared_axes=None, input_shape=None, name=None):
    return _cfg("PReLU", input_shape, name, shared_axes=shared_axes)


def Softmax(axis=-1, input_shape=None, name=None):
    return _cfg("Softmax", input_shape, name, axis=axis)


def SpatialDropout1D(rate=0.5, input_shape=None, name=None):
    return _cfg("SpatialDropout1D", input_shape, name, rate=rate)


def SpatialDropout2D(rate=0.5, input_shape=None, name=None):
    return _cfg("SpatialDropout2D", input_shape, name, rate=rate)


# ------------------------------------------------------------------ model
class Sequential(KerasModel):
    """Shape-inferring Sequential over layer configs (reference:
    nn/keras/Sequential.scala — layers resolve dims at add/build time).
    Lazily built: the module tree materializes on first use, then all of
    KerasModel's compile/fit/evaluate/predict applies."""

    def __init__(self, *layers, name: str = "sequential"):
        super().__init__(module=None)
        self._specs = list(layers)
        self._name = name
        self._loaded = None

    def add(self, layer_cfg: dict) -> "Sequential":
        if self._loaded is not None:
            raise RuntimeError("model already built — add() before "
                               "fit/predict/build")
        self._specs.append(layer_cfg)
        return self

    def build(self, rng=None) -> "Sequential":
        from bigdl_tpu.interop.keras_loader import _build_sequential
        if self._loaded is None:
            self._loaded = _build_sequential(self._specs)
            self.module = self._loaded.module
            self.module.name = self._name
            self.params, self.model_state = self._loaded.init(rng)
        return self

    def _shape_walk(self):
        """Yield (class_name, module_or_None, out_shape) per layer config —
        the single shape-replay used by output_shape and summary."""
        from bigdl_tpu.interop import keras_loader as kl
        shape = None
        for spec in self._specs:
            cls, cfg = spec["class_name"], spec.get("config", {})
            if shape is None and cls != "InputLayer":
                bis = cfg.get("batch_input_shape") or cfg.get("batch_shape")
                if bis is None:
                    raise ValueError("first keras layer carries no "
                                     "input_shape")
                shape = tuple(bis)
            module, shape, _ = kl._build_layer(cls, cfg, [shape])
            yield cls, module, shape

    @property
    def output_shape(self):
        shape = None
        for _, _, shape in self._shape_walk():
            pass
        return shape

    # KerasModel entry points build lazily
    def compile(self, *a, **kw):
        self.build()
        return super().compile(*a, **kw)

    def fit(self, *a, **kw):
        self.build()
        return super().fit(*a, **kw)

    def evaluate(self, *a, **kw):
        self.build()
        return super().evaluate(*a, **kw)

    def predict(self, *a, **kw):
        self.build()
        return super().predict(*a, **kw)

    def save(self, path: str):
        self.build()
        return super().save(path)

    @classmethod
    def load(cls, path: str) -> KerasModel:
        """Load a saved model. Returns a plain KerasModel — the layer
        configs are not round-tripped through the serializer, but the
        module tree and weights are."""
        return KerasModel.load(path)

    def summary(self) -> str:
        """Per-layer output shapes + param counts (reference:
        KerasNet.summary)."""
        self.build()
        lines = [f"{'layer':<28} {'output shape':<20} {'params':>10}"]
        total = 0
        idx = 0
        for cls_name, module, shape in self._shape_walk():
            if module is None:
                continue
            p = self.params.get(str(idx), {})
            n = sum(int(l.size) for l in jax.tree.leaves(p))
            total += n
            lines.append(f"{cls_name:<28} {str(shape):<20} {n:>10}")
            idx += 1
        lines.append(f"total params: {total}")
        return "\n".join(lines)


class Model(KerasModel):
    """Functional model over symbolic tensors (reference:
    nn/keras/Model.scala / Topology.scala):

        x = kl.Input((8,))
        a = kl.Dense(16, activation="relu")(x)
        b = kl.Dense(16, activation="tanh")(x)
        y = kl.Dense(2)(kl.Concatenate()(a, b))
        model = kl.Model(x, y)

    Built lazily through the importer's functional builder, so every dim
    is inferred."""

    def __init__(self, inputs, outputs, name: str = "model"):
        super().__init__(module=None)
        self._inputs = inputs if isinstance(inputs, (list, tuple)) \
            else [inputs]
        self._outputs = outputs if isinstance(outputs, (list, tuple)) \
            else [outputs]
        self._name = name
        self._built = False

    def _config(self) -> dict:
        layers, seen = [], set()

        def visit(t: KTensor):
            if id(t) in seen:
                return
            seen.add(id(t))
            for p in t.inputs:
                visit(p)
            layers.append({
                "name": t.name,
                "class_name": t.layer["class_name"],
                "config": dict(t.layer["config"]),
                "inbound_nodes":
                    [[[p.name, 0, 0, {}] for p in t.inputs]]
                    if t.inputs else [],
            })
        for o in self._outputs:
            visit(o)
        for i in self._inputs:
            if id(i) not in seen:
                raise ValueError(f"input {i.name!r} is not connected to "
                                 f"any output")
        return {"class_name": "Model", "config": {
            "name": self._name,
            "layers": layers,
            "input_layers": [[i.name, 0, 0] for i in self._inputs],
            "output_layers": [[o.name, 0, 0] for o in self._outputs],
        }}

    def build(self, rng=None) -> "Model":
        if not self._built:
            from bigdl_tpu.interop.keras_loader import _build_from_config
            loaded = _build_from_config(self._config())
            self.module = loaded.module
            self.params, self.model_state = loaded.init(rng)
            self._built = True
        return self

    def compile(self, *a, **kw):
        self.build()
        return super().compile(*a, **kw)

    def fit(self, *a, **kw):
        self.build()
        return super().fit(*a, **kw)

    def evaluate(self, *a, **kw):
        self.build()
        return super().evaluate(*a, **kw)

    def predict(self, *a, **kw):
        self.build()
        return super().predict(*a, **kw)

    def save(self, path: str):
        self.build()
        return super().save(path)


# Model.load cannot reconstruct the symbolic graph; return a plain
# KerasModel (module tree + weights round-trip, like Sequential.load)
Model.load = classmethod(lambda cls, path: KerasModel.load(path))


# ------------------------------------------------------- keras-1 tail
def Cropping1D(cropping=(1, 1), input_shape=None, name=None):
    return _cfg("Cropping1D", input_shape, name, cropping=cropping)


def Cropping2D(cropping=((0, 0), (0, 0)), input_shape=None, name=None):
    return _cfg("Cropping2D", input_shape, name, cropping=cropping)


def Cropping3D(cropping=((1, 1), (1, 1), (1, 1)), input_shape=None,
               name=None):
    return _cfg("Cropping3D", input_shape, name, cropping=cropping)


def MaxPooling3D(pool_size=(2, 2, 2), strides=None, input_shape=None,
                 name=None):
    return _cfg("MaxPooling3D", input_shape, name, pool_size=pool_size,
                strides=strides)


def AveragePooling3D(pool_size=(2, 2, 2), strides=None, input_shape=None,
                     name=None):
    return _cfg("AveragePooling3D", input_shape, name, pool_size=pool_size,
                strides=strides)


def AveragePooling1D(pool_size=2, strides=None, input_shape=None, name=None):
    return _cfg("AveragePooling1D", input_shape, name, pool_size=pool_size,
                strides=strides)


def GlobalAveragePooling3D(input_shape=None, name=None):
    return _cfg("GlobalAveragePooling3D", input_shape, name)


def GlobalMaxPooling3D(input_shape=None, name=None):
    return _cfg("GlobalMaxPooling3D", input_shape, name)


def UpSampling1D(size=2, input_shape=None, name=None):
    return _cfg("UpSampling1D", input_shape, name, size=size)


def UpSampling3D(size=(2, 2, 2), input_shape=None, name=None):
    return _cfg("UpSampling3D", input_shape, name, size=size)


def ZeroPadding1D(padding=1, input_shape=None, name=None):
    return _cfg("ZeroPadding1D", input_shape, name, padding=padding)


def ZeroPadding3D(padding=(1, 1, 1), input_shape=None, name=None):
    return _cfg("ZeroPadding3D", input_shape, name, padding=padding)


def ThresholdedReLU(theta=1.0, input_shape=None, name=None):
    return _cfg("ThresholdedReLU", input_shape, name, theta=theta)


def GaussianNoise(stddev, input_shape=None, name=None):
    return _cfg("GaussianNoise", input_shape, name, stddev=stddev)


def GaussianDropout(rate, input_shape=None, name=None):
    return _cfg("GaussianDropout", input_shape, name, rate=rate)


def SpatialDropout3D(rate, input_shape=None, name=None):
    return _cfg("SpatialDropout3D", input_shape, name, rate=rate)


def Conv3D(filters, kernel_size, strides=(1, 1, 1), padding="valid",
           activation=None, use_bias=True, input_shape=None, name=None):
    # padding flows into the config so the builder raises LOUDLY on
    # "same" (unsupported) instead of silently building a valid conv
    return _cfg("Conv3D", input_shape, name, filters=filters,
                kernel_size=kernel_size, strides=strides, padding=padding,
                activation=activation, use_bias=use_bias)


def LocallyConnected1D(filters, kernel_size, strides=1, activation=None,
                       use_bias=True, input_shape=None, name=None):
    return _cfg("LocallyConnected1D", input_shape, name, filters=filters,
                kernel_size=kernel_size, strides=strides,
                activation=activation, use_bias=use_bias)


def LocallyConnected2D(filters, kernel_size, strides=1, activation=None,
                       use_bias=True, input_shape=None, name=None):
    return _cfg("LocallyConnected2D", input_shape, name, filters=filters,
                kernel_size=kernel_size, strides=strides,
                activation=activation, use_bias=use_bias)


def ConvLSTM2D(filters, kernel_size, return_sequences=False, peephole=True,
               input_shape=None, name=None):
    return _cfg("ConvLSTM2D", input_shape, name, filters=filters,
                kernel_size=kernel_size, return_sequences=return_sequences,
                peephole=peephole)


# keras-1 constructors (reference targets keras 1.2.2) — these take the
# keras-1 POSITIONAL signatures (nb_filter, nb_row, nb_col, ...); plain
# aliases would misbind nb_col into `strides`
def Convolution2D(nb_filter, nb_row, nb_col=None, activation=None,
                  border_mode="valid", subsample=(1, 1), bias=True,
                  input_shape=None, name=None):
    if nb_col is None:                  # keras-2 style: Conv2D(f, (3, 3))
        return Conv2D(nb_filter, nb_row, activation=activation,
                      padding=border_mode, strides=subsample, use_bias=bias,
                      input_shape=input_shape, name=name)
    return Conv2D(nb_filter, (nb_row, nb_col), strides=subsample,
                  padding=border_mode, activation=activation, use_bias=bias,
                  input_shape=input_shape, name=name)


def Convolution1D(nb_filter, filter_length, activation=None,
                  border_mode="valid", subsample_length=1, bias=True,
                  input_shape=None, name=None):
    return Conv1D(nb_filter, filter_length, strides=subsample_length,
                  padding=border_mode, activation=activation, use_bias=bias,
                  input_shape=input_shape, name=name)


def Convolution3D(nb_filter, kernel_dim1, kernel_dim2=None, kernel_dim3=None,
                  activation=None, border_mode="valid", subsample=(1, 1, 1),
                  bias=True, input_shape=None, name=None):
    if kernel_dim2 is None:             # keras-2 style: Conv3D(f, (k,k,k))
        ks = kernel_dim1
    else:
        ks = (kernel_dim1, kernel_dim2, kernel_dim3)
    return Conv3D(nb_filter, ks, strides=subsample, padding=border_mode,
                  activation=activation, use_bias=bias,
                  input_shape=input_shape, name=name)


def Deconvolution2D(nb_filter, nb_row, nb_col=None, output_shape=None,
                    activation=None, border_mode="valid", subsample=(1, 1),
                    bias=True, input_shape=None, name=None):
    # keras-1's REQUIRED 4th positional `output_shape` is accepted (and
    # checked against our inferred shape at build time being unnecessary —
    # the loader infers output shapes itself); omitting it from the
    # signature would misbind the tuple into `activation`
    ks = nb_row if nb_col is None else (nb_row, nb_col)
    return Conv2DTranspose(nb_filter, ks, strides=subsample,
                           padding=border_mode, activation=activation,
                           use_bias=bias, input_shape=input_shape, name=name)


def AtrousConvolution2D(nb_filter, nb_row, nb_col=None, atrous_rate=(1, 1),
                        activation=None, border_mode="valid",
                        subsample=(1, 1), bias=True, input_shape=None,
                        name=None):
    ks = nb_row if nb_col is None else (nb_row, nb_col)
    cfg = Conv2D(nb_filter, ks, strides=subsample, padding=border_mode,
                 activation=activation, use_bias=bias,
                 input_shape=input_shape, name=name)
    cfg["config"]["dilation_rate"] = tuple(atrous_rate) \
        if isinstance(atrous_rate, (list, tuple)) else (atrous_rate,) * 2
    return cfg


def AtrousConvolution1D(nb_filter, filter_length, atrous_rate=1,
                        activation=None, border_mode="valid",
                        subsample_length=1, bias=True, input_shape=None,
                        name=None):
    if atrous_rate not in (1, (1,), [1]):
        # fail at the call site, not at distant build time: the Conv1D
        # builder has no dilated path (use AtrousConvolution2D on a
        # width-1 reshape for dilated 1-D convs)
        raise NotImplementedError(
            f"AtrousConvolution1D: atrous_rate={atrous_rate!r} is not "
            f"supported (1-D dilation has no builder)")
    return Conv1D(nb_filter, filter_length, strides=subsample_length,
                  padding=border_mode, activation=activation, use_bias=bias,
                  input_shape=input_shape, name=name)


SeparableConvolution2D = SeparableConv2D


SoftMax = Softmax                       # keras-1 spelling (nn/keras/SoftMax)
