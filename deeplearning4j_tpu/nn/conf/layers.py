"""Layer configurations + their functional TPU implementations.

Reference: `deeplearning4j-nn/.../nn/conf/layers/` (declarative configs,
~21 types) and `nn/layers/` (implementations). This build merges the two:
each config dataclass is JSON-serializable (like the reference's Jackson
polymorphic configs, `NeuralNetConfiguration.java:478`) AND carries the pure
functional math (`init_params` / `forward`) that the network composes into a
single jitted XLA step. Hand-written `backpropGradient` methods
(`BaseLayer.java:144`) have no equivalent here — `jax.grad` differentiates
the whole composed forward.

Layout conventions (TPU-native): FF activations (B, F); CNN activations NHWC
(vs. the reference's cuDNN NCHW); RNN activations (B, T, F) (vs. reference
(B, F, T)).
"""
from __future__ import annotations

import dataclasses
import enum
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import (
    InputType,
    InputTypeConvolutional,
    InputTypeConvolutionalFlat,
    InputTypeFeedForward,
    InputTypeRecurrent,
)
from deeplearning4j_tpu.nn.layers.recurrent import lstm_forward, lstm_step
from deeplearning4j_tpu.nn.updater import (
    GradientNormalization,
    Updater,
    UpdaterConfig,
)
from deeplearning4j_tpu.nn.weights import Distribution, WeightInit, init_weights
from deeplearning4j_tpu.ops.activations import Activation, activation_fn
from deeplearning4j_tpu.ops.losses import LossFunction, loss_score
from deeplearning4j_tpu.util.conv_utils import (
    ConvolutionMode,
    PoolingType,
    conv_output_hw,
    explicit_padding,
)

Params = Dict[str, jnp.ndarray]
State = Dict[str, jnp.ndarray]

# ---------------------------------------------------------------------------
# serde registry


_LAYER_REGISTRY: Dict[str, type] = {}

# field-name → decoder applied on from_json (encoders: Enum→.value, etc.)
_FIELD_DECODERS: Dict[str, Callable[[Any], Any]] = {
    "activation": Activation,
    "gate_activation": Activation,
    "expert_activation": Activation,
    "weight_init": WeightInit,
    "dist": Distribution.from_json,
    "loss": LossFunction,
    "updater": Updater,
    "pooling_type": PoolingType,
    "convolution_mode": ConvolutionMode,
    "gradient_normalization": GradientNormalization,
    "updater_cfg": UpdaterConfig.from_json,
    "kernel": tuple,
    "stride": tuple,
    "padding": tuple,
    "dilation": tuple,
}


def register_layer(cls):
    _LAYER_REGISTRY[cls.TYPE] = cls
    return cls


def _encode(v):
    import enum as _enum

    if isinstance(v, _enum.Enum):
        return v.value
    if hasattr(v, "to_json"):  # Distribution, UpdaterConfig, ReconstructionDistribution, …
        return v.to_json()
    if isinstance(v, tuple):
        return list(v)
    return v


def layer_to_json(layer: "Layer") -> dict:
    d = {"type": layer.TYPE}
    for f in dataclasses.fields(layer):
        d[f.name] = _encode(getattr(layer, f.name))
    return d


def layer_from_json(d: dict) -> "Layer":
    d = dict(d)
    t = d.pop("type")
    cls = _LAYER_REGISTRY[t]
    kwargs = {}
    names = {f.name for f in dataclasses.fields(cls)}
    for k, v in d.items():
        if k not in names:
            continue
        if v is not None and k in _FIELD_DECODERS:
            v = _FIELD_DECODERS[k](v)
        kwargs[k] = v
    return cls(**kwargs)


# ---------------------------------------------------------------------------
# base


@dataclass
class Layer:
    """Base layer config (reference `nn/conf/layers/Layer.java` +
    `BaseLayer` hyperparameter fields)."""

    TYPE = "base"

    name: Optional[str] = None
    # None ⇒ inherit the global builder default at build() time
    # (reference: `NeuralNetConfiguration.ListBuilder.build` merging)
    activation: Optional[Activation] = None
    weight_init: Optional[WeightInit] = None
    dist: Optional[Distribution] = None
    bias_init: Optional[float] = None
    dropout: Optional[float] = None  # keep-independent drop prob, 0 = off
    # DropConnect: mask the weight matrix instead of the input (reference
    # `NeuralNetConfiguration.useDropConnect` + `BaseLayer.preOutput:369`)
    use_drop_connect: Optional[bool] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    # fully-resolved per-layer updater config, populated at build()
    updater_cfg: Optional[UpdaterConfig] = None
    learning_rate: Optional[float] = None
    bias_learning_rate: Optional[float] = None

    # -- contract -----------------------------------------------------------
    input_kind = "any"  # 'ff' | 'cnn' | 'rnn' | 'any' — drives preprocessor auto-insertion

    @property
    def has_params(self) -> bool:
        return True

    def output_type(self, it: InputType) -> InputType:
        raise NotImplementedError

    def init_params(self, key: jax.Array, it: InputType, dtype=jnp.float32) -> Params:
        return {}

    def init_state(self, it: InputType) -> State:
        return {}

    def forward(self, params: Params, state: State, x: jnp.ndarray, *,
                train: bool = False, rng: Optional[jax.Array] = None,
                mask: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, State]:
        raise NotImplementedError

    def param_flags(self, name: str) -> Dict[str, bool]:
        """is_bias → bias LR + bias l1/l2 apply; regularizable → l1/l2 apply.
        (reference: ParamInitializer weight/bias key split, `nn/params/`)."""
        is_bias = name in ("b", "vb", "beta")
        return {"is_bias": is_bias, "regularizable": not is_bias and name != "gamma"}

    # -- helpers ------------------------------------------------------------
    def _act(self):
        return activation_fn(self.activation or Activation.IDENTITY)

    def _maybe_dropout(self, x, train, rng):
        """Input dropout (reference applies dropout to layer INPUT in
        `BaseLayer.preOutput:354` via `Dropout.applyDropout`). DL4J keeps
        E[x] by inverted dropout: scale by 1/keep at train time.

        Inside a `row_offset_scope` (pipeline microbatches, any manual
        shard_map slicing the batch) the mask is drawn from per-ROW keys
        (`fold_in(rng, global_row)`, see `ops/rng_rows`) so the
        realization is invariant to how the batch is partitioned — a
        GPipe microbatch reproduces exactly the rows the global batch
        would draw, which is what makes pipeline training with dropout
        hold same-seed parity. OUTSIDE any scope (single device, dp
        shards under the one global-view jit — where a single bulk draw
        is already partition-invariant because there is only one trace
        of the whole batch) the mask is ONE bulk bernoulli: the per-row
        fold_in+vmap stream costs B extra threefry key derivations plus
        a vmapped draw per dropout site, pure overhead on the
        single-device path (priced every round by bench gpt_med's
        `dropout_rng_overhead_pct`). To reproduce pipeline masks on one
        device, trace under `row_offset_scope(0)` — how the parity
        tests pin same-seed equality."""
        p = self.dropout or 0.0
        if not train or p <= 0.0 or rng is None:
            return x
        from deeplearning4j_tpu.ops.rng_rows import current_row_offset

        keep = 1.0 - p
        off = current_row_offset()
        if off is None:  # single-device/global-view: one bulk draw
            m = jax.random.bernoulli(rng, keep, x.shape)
            return jnp.where(m, x / keep, 0.0)
        rows = jnp.arange(x.shape[0], dtype=jnp.int32) \
            + jnp.asarray(off, jnp.int32)
        keys = jax.vmap(lambda r: jax.random.fold_in(rng, r))(rows)
        m = jax.vmap(
            lambda kk: jax.random.bernoulli(kk, keep, x.shape[1:]))(keys)
        return jnp.where(m, x / keep, 0.0)

    def _maybe_drop_connect(self, W, train, rng):
        """DropConnect: the WEIGHT matrix gets the dropout mask instead of
        the input (reference `BaseLayer.preOutput:369-370` →
        `Dropout.applyDropConnect` when `useDropConnect` is set). Inverted
        scaling keeps E[W]."""
        p = self.dropout or 0.0
        if not train or p <= 0.0 or rng is None:
            return W
        keep = 1.0 - p
        m = jax.random.bernoulli(jax.random.fold_in(rng, 1), keep, W.shape)
        return jnp.where(m, W / keep, 0.0)

    def _winit(self, key, shape, fan_in, fan_out, dtype):
        return init_weights(key, shape, fan_in, fan_out,
                            self.weight_init or WeightInit.XAVIER, self.dist, dtype)


class FeedForwardLayer(Layer):
    """Base for layers with n_in/n_out (reference
    `nn/conf/layers/FeedForwardLayer.java`)."""

    n_in: int = 0
    n_out: int = 0


# ---------------------------------------------------------------------------
# dense / output


@register_layer
@dataclass
class DenseLayer(FeedForwardLayer):
    """Fully-connected layer (reference `nn/conf/layers/DenseLayer.java`,
    impl `nn/layers/feedforward/dense/DenseLayer.java` via
    `BaseLayer.preOutput:354` = W·x+b)."""

    TYPE = "dense"
    input_kind = "ff"
    n_in: int = 0
    n_out: int = 0

    def output_type(self, it: InputType) -> InputType:
        if isinstance(it, InputTypeRecurrent):
            # time-distributed dense (reference inserts RnnToFF/FFToRnn pair;
            # here the matmul broadcasts over time natively)
            return InputType.recurrent(self.n_out, it.timeseries_length)
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        kW, _ = jax.random.split(key)
        W = self._winit(kW, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)
        b = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return {"W": W, "b": b}

    def pre_output(self, params, x, *, train=False, rng=None):
        W = params["W"]
        if self.use_drop_connect:
            # reference semantics: DropConnect REPLACES input dropout
            # (BaseLayer.preOutput:485 gates input dropout on
            # !isUseDropConnect)
            W = self._maybe_drop_connect(W, train, rng)
        else:
            x = self._maybe_dropout(x, train, rng)
        return x @ W + params["b"]

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._act()(self.pre_output(params, x, train=train, rng=rng)), state


@register_layer
@dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (reference `nn/conf/layers/OutputLayer.java`,
    impl `nn/layers/OutputLayer.java` / `BaseOutputLayer`)."""

    TYPE = "output"
    loss: LossFunction = LossFunction.MCXENT

    def loss_score(self, params, x, labels, *, train=False, rng=None, mask=None):
        pre = self.pre_output(params, x, train=train, rng=rng)
        if pre.ndim == 3:  # time-distributed: flatten rows, expand mask
            B, T, F = pre.shape
            pre = pre.reshape(B * T, F)
            # sparse int labels are (B, T); dense targets — one-hot OR 2-D
            # float regression targets — keep a feature axis
            labels = (labels.reshape(B * T)
                      if labels.ndim == 2
                      and jnp.issubdtype(labels.dtype, jnp.integer)
                      else labels.reshape(B * T, -1))
            if mask is not None:
                mask = mask.reshape(B * T)
        return loss_score(self.loss, self.activation or Activation.IDENTITY,
                          labels, pre, mask)

    def score_array(self, params, x, labels, *, mask=None):
        """Per-EXAMPLE scores, shape (B,) — the reference's
        `ILossFunction.computeScoreArray` consumed by
        `MultiLayerNetwork.scoreExamples`. Time-distributed outputs sum
        their (masked) per-timestep rows into one score per sequence
        (reference `RnnOutputLayer` computeScoreForExamples semantics)."""
        from deeplearning4j_tpu.ops.losses import loss_per_row

        pre = self.pre_output(params, x, train=False, rng=None)
        per_row = loss_per_row(self.loss,
                               self.activation or Activation.IDENTITY,
                               labels, pre)
        if mask is not None:
            per_row = per_row * jnp.reshape(mask, per_row.shape)
        if per_row.ndim > 1:  # (B, T) time-distributed → sum over time
            per_row = jnp.sum(per_row.reshape(per_row.shape[0], -1), axis=-1)
        return per_row


@register_layer
@dataclass
class RnnOutputLayer(OutputLayer):
    """Per-timestep output layer (reference
    `nn/conf/layers/RnnOutputLayer.java`): labels are (B, T, nOut), score is
    masked mean over valid (b, t) rows. `has_bias=False` is the
    bias-free head of the composed-block language models: it holds `W`
    alone, and its product accumulates and comes out in float32
    whatever `W`'s dtype, as the tied head's does."""

    TYPE = "rnn_output"
    input_kind = "rnn"
    has_bias: bool = True

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        p = super().init_params(key, it, dtype)
        if not self.has_bias:
            del p["b"]
        return p

    def pre_output(self, params, x, *, train=False, rng=None):
        if self.has_bias:
            return super().pre_output(params, x, train=train, rng=rng)
        x = self._maybe_dropout(x, train, rng)
        return jnp.einsum("...d,dv->...v", x, params["W"],
                          preferred_element_type=jnp.float32)

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length if isinstance(it, InputTypeRecurrent) else -1
        return InputType.recurrent(self.n_out, t)


@register_layer
@dataclass
class TiedRnnOutputLayer(RnnOutputLayer):
    """Per-timestep output head tied to an embedding: logits are
    `x E^T / logits_scaling` with `E` the `W` of layer `tied_to` (a
    `TokenEmbedding`), so the head holds no parameters of its own and
    the table's gradient is the sum over both of its uses. The network
    hands this layer the tied layer's parameters
    (`MultiLayerNetwork._params_of`). The product accumulates and comes
    out in float32 whatever the table's dtype: a vocabulary of 1e5
    logits in bfloat16 would tie at the top."""

    TYPE = "tied_rnn_output"
    tied_to: int = 0
    logits_scaling: float = 1.0

    @property
    def has_params(self) -> bool:
        return False

    def pre_output(self, params, x, *, train=False, rng=None):
        x = self._maybe_dropout(x, train, rng)
        z = jnp.einsum("...d,vd->...v", x, params["W"],
                       preferred_element_type=jnp.float32)
        return z / self.logits_scaling


@register_layer
@dataclass
class LossLayer(Layer):
    """Parameter-free loss head (reference `nn/conf/layers/LossLayer.java`)."""

    TYPE = "loss"
    loss: LossFunction = LossFunction.MCXENT

    @property
    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        return it

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._act()(x), state

    def pre_output(self, params, x, *, train=False, rng=None):
        return x

    def loss_score(self, params, x, labels, *, train=False, rng=None, mask=None):
        pre = self.pre_output(params, x)
        if pre.ndim == 3:
            B, T, F = pre.shape
            pre = pre.reshape(B * T, F)
            # sparse int labels are (B, T); dense targets — one-hot OR 2-D
            # float regression targets — keep a feature axis
            labels = (labels.reshape(B * T)
                      if labels.ndim == 2
                      and jnp.issubdtype(labels.dtype, jnp.integer)
                      else labels.reshape(B * T, -1))
            if mask is not None:
                mask = mask.reshape(B * T)
        return loss_score(self.loss, self.activation or Activation.IDENTITY,
                          labels, pre, mask)

    # per-example scoring shares OutputLayer's implementation (it only
    # touches pre_output/loss/activation, which LossLayer also carries)
    score_array = OutputLayer.score_array


# ---------------------------------------------------------------------------
# convolutional


@register_layer
@dataclass
class ConvolutionLayer(FeedForwardLayer):
    """2D convolution (reference `nn/conf/layers/ConvolutionLayer.java`,
    impl `nn/layers/convolution/ConvolutionLayer.java:52`).

    The reference's CPU path is im2col+GEMM (`ConvolutionLayer.java:166-212`)
    with an optional cuDNN helper (`CudnnConvolutionHelper.java:49`). Here the
    conv lowers directly to XLA `conv_general_dilated` — the TPU-native
    'helper path' — which XLA tiles onto the MXU; there is no im2col
    materialization and no helper/fallback split to maintain.
    """

    TYPE = "convolution"
    input_kind = "cnn"
    n_in: int = 0  # in channels (inferred from input type if 0)
    n_out: int = 0  # out channels
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: ConvolutionMode = ConvolutionMode.TRUNCATE

    def _in_channels(self, it: InputType) -> int:
        if isinstance(it, InputTypeConvolutional):
            return it.channels
        return self.n_in

    def output_type(self, it: InputType) -> InputType:
        assert isinstance(it, InputTypeConvolutional), f"conv needs CNN input, got {it}"
        oh, ow = conv_output_hw((it.height, it.width), self.kernel, self.stride,
                                self.padding, self.convolution_mode, self.dilation)
        return InputType.convolutional(oh, ow, self.n_out)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        cin = self._in_channels(it)
        kh, kw = self.kernel
        fan_in = cin * kh * kw
        fan_out = self.n_out * kh * kw
        W = self._winit(key, (kh, kw, cin, self.n_out), fan_in, fan_out, dtype)
        b = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return {"W": W, "b": b}

    def pre_output(self, params, x, *, train=False, rng=None, input_hw=None):
        x = self._maybe_dropout(x, train, rng)
        pad = explicit_padding((x.shape[1], x.shape[2]), self.kernel, self.stride,
                               self.padding, self.convolution_mode, self.dilation)
        y = lax.conv_general_dilated(
            x, params["W"],
            window_strides=self.stride,
            padding=pad,
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + params["b"]

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._act()(self.pre_output(params, x, train=train, rng=rng)), state


@register_layer
@dataclass
class SubsamplingLayer(Layer):
    """Pooling (reference `nn/conf/layers/SubsamplingLayer.java`, impl
    `nn/layers/convolution/subsampling/SubsamplingLayer.java`; cuDNN helper
    `CudnnSubsamplingHelper.java`). Lowers to XLA reduce_window."""

    TYPE = "subsampling"
    input_kind = "cnn"
    pooling_type: PoolingType = PoolingType.MAX
    kernel: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: ConvolutionMode = ConvolutionMode.TRUNCATE
    pnorm: int = 2

    @property
    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        assert isinstance(it, InputTypeConvolutional)
        oh, ow = conv_output_hw((it.height, it.width), self.kernel, self.stride,
                                self.padding, self.convolution_mode)
        return InputType.convolutional(oh, ow, it.channels)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        pad = explicit_padding((x.shape[1], x.shape[2]), self.kernel, self.stride,
                               self.padding, self.convolution_mode)
        window = (1, self.kernel[0], self.kernel[1], 1)
        strides = (1, self.stride[0], self.stride[1], 1)
        pads = ((0, 0), pad[0], pad[1], (0, 0))
        if self.pooling_type == PoolingType.MAX:
            y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        elif self.pooling_type == PoolingType.AVG:
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
            y = s / (self.kernel[0] * self.kernel[1])
        elif self.pooling_type == PoolingType.SUM:
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        elif self.pooling_type == PoolingType.PNORM:
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, pads)
            y = s ** (1.0 / p)
        else:
            raise ValueError(self.pooling_type)
        return y, state


# ---------------------------------------------------------------------------
# normalization


@register_layer
@dataclass
class BatchNormalization(FeedForwardLayer):
    """Batch norm (reference `nn/conf/layers/BatchNormalization.java`, impl
    `nn/layers/normalization/BatchNormalization.java:41`; cuDNN helper
    `CudnnBatchNormalizationHelper.java`). Running mean/var live in the layer
    STATE pytree threaded through the jitted step (the reference stores them
    as non-gradient params)."""

    TYPE = "batchnorm"
    n_in: int = 0
    n_out: int = 0
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False

    def output_type(self, it: InputType) -> InputType:
        return it

    def _nf(self, it: Optional[InputType]) -> int:
        if isinstance(it, InputTypeConvolutional):
            return it.channels
        if isinstance(it, (InputTypeRecurrent, InputTypeFeedForward)):
            return it.size
        # no resolved input type: fall back to the explicitly configured size
        n = self.n_out or self.n_in
        if not n:
            raise ValueError(
                "BatchNormalization needs either a resolved InputType "
                "(set_input_type(s) on the builder) or an explicit n_in/n_out")
        return n

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        nf = self._nf(it)
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.ones((nf,), dtype), "beta": jnp.zeros((nf,), dtype)}

    def init_state(self, it: InputType) -> State:
        nf = self._nf(it)
        return {"mean": jnp.zeros((nf,), jnp.float32),
                "var": jnp.ones((nf,), jnp.float32)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        axes = tuple(range(x.ndim - 1))  # all but channel/feature (last)
        # batch statistics in >= f32 (REDUCTION accumulation dtype — no
        # f32 copy of the activation is materialized): under bf16 mixed
        # precision, bf16-reduced mean/var would feed noisy stats into both
        # normalization and the carried running stats. The normalization
        # itself is then folded to ONE fused multiply-add y = x*scale+bias
        # with per-channel f32 scale/bias cast to the activation dtype —
        # under bf16 this halves the layer's HBM traffic vs normalizing an
        # f32 upcast of x (ResNet-50 has 53 of these on the trunk).
        # promote (not force-f32) so f64 gradient checks keep f64
        stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
        if train:
            # ONE fused pass over x for both statistics: jnp.var would
            # re-walk the activation after the mean (two multi-MB sweeps
            # per BN; the trunk's 53 BN reductions dominated the ResNet-50
            # profile). Shifted one-pass variance
            #   var = E[(x-m0)^2] - (mean-m0)^2,   m0 = running mean
            # is algebraically the exact batch variance for ANY shift, and
            # centering by the running mean keeps it well-conditioned even
            # when |mean| >> std (plain E[x^2]-mean^2 would cancel
            # catastrophically there). XLA multi-output-fuses the two
            # reductions into one sweep; f32 accumulation.
            m0 = jax.lax.stop_gradient(state["mean"]).astype(x.dtype)
            xc = x - m0
            mean_c = jnp.mean(xc, axis=axes, dtype=stat_dtype)
            msq_c = jnp.mean(lax.square(xc), axis=axes, dtype=stat_dtype)
            var = jnp.maximum(msq_c - lax.square(mean_c), 0.0)
            mean = mean_c + m0.astype(stat_dtype)
            d = self.decay
            new_state = {"mean": d * state["mean"] + (1 - d) * mean,
                         "var": d * state["var"] + (1 - d) * var}
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        scale = jax.lax.rsqrt(var.astype(stat_dtype) + self.eps)
        if not self.lock_gamma_beta:
            scale = scale * params["gamma"].astype(stat_dtype)
        bias = -mean.astype(stat_dtype) * scale
        if not self.lock_gamma_beta:
            bias = bias + params["beta"].astype(stat_dtype)
        y = x * scale.astype(x.dtype) + bias.astype(x.dtype)
        return self._act()(y), new_state

    def param_flags(self, name):
        # gamma/beta: no l1/l2 by default (reference BatchNormalizationParamInitializer)
        return {"is_bias": name == "beta", "regularizable": False}


@register_layer
@dataclass
class LocalResponseNormalization(Layer):
    """Across-channel LRN (reference
    `nn/conf/layers/LocalResponseNormalization.java`, impl
    `nn/layers/normalization/LocalResponseNormalization.java`; cuDNN helper
    `CudnnLocalResponseNormalizationHelper.java`):
    y = x / (k + alpha * sum_{window n} x^2)^beta."""

    TYPE = "lrn"
    input_kind = "cnn"
    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    @property
    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        return it

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        half = self.n // 2
        sq = x**2
        s = lax.reduce_window(sq, 0.0, lax.add,
                              (1, 1, 1, self.n), (1, 1, 1, 1),
                              ((0, 0), (0, 0), (0, 0), (half, self.n - 1 - half)))
        return x / (self.k + self.alpha * s) ** self.beta, state


# ---------------------------------------------------------------------------
# recurrent


@register_layer
@dataclass
class GravesLSTM(FeedForwardLayer):
    """Graves-style peephole LSTM (reference
    `nn/conf/layers/GravesLSTM.java`, math in
    `nn/layers/recurrent/LSTMHelpers.java:58`). See
    `nn/layers/recurrent.py` for the lax.scan lowering."""

    TYPE = "graves_lstm"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    gate_activation: Activation = Activation.SIGMOID
    forget_gate_bias_init: float = 1.0

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length if isinstance(it, InputTypeRecurrent) else -1
        return InputType.recurrent(self.n_out, t)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        kW, kR, kP = jax.random.split(key, 3)
        n_in, n_out = self.n_in, self.n_out
        W = self._winit(kW, (n_in, 4 * n_out), n_in, n_out, dtype)
        RW = self._winit(kR, (n_out, 4 * n_out), n_out, n_out, dtype)
        b = jnp.zeros((4 * n_out,), dtype)
        # forget-gate bias init (gate order [i, f, o, g]; reference
        # GravesLSTMParamInitializer sets forget-gate slice to forgetGateBiasInit)
        b = b.at[n_out:2 * n_out].set(self.forget_gate_bias_init)
        return {"W": W, "RW": RW, "b": b,
                "pI": jnp.zeros((n_out,), dtype),
                "pF": jnp.zeros((n_out,), dtype),
                "pO": jnp.zeros((n_out,), dtype)}

    def param_flags(self, name):
        is_bias = name == "b"
        return {"is_bias": is_bias, "regularizable": name in ("W", "RW")}

    def _acts(self):
        return activation_fn(self.gate_activation), activation_fn(self.activation or Activation.TANH)

    def _act_kinds(self):
        """Static activation identities for the fused-kernel dispatch."""
        return (self.gate_activation == Activation.SIGMOID,
                (self.activation or Activation.TANH) == Activation.TANH)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        gate_act, cell_act = self._acts()
        gk, ck = self._act_kinds()
        peep = (params["pI"], params["pF"], params["pO"])
        h0 = state.get("h") if state else None
        c0 = state.get("c") if state else None
        out, (hT, cT) = lstm_forward(x, params["W"], params["RW"], params["b"],
                                     peep, gate_act, cell_act, h0, c0, mask,
                                     gate_is_sigmoid=gk, cell_is_tanh=ck)
        return out, {"h": hT, "c": cT} if state else state

    def step(self, params, x_t, h_prev, c_prev):
        """Single-timestep inference (reference `rnnTimeStep`)."""
        gate_act, cell_act = self._acts()
        peep = (params["pI"], params["pF"], params["pO"])
        return lstm_step(x_t, params["W"], params["RW"], params["b"], peep,
                         gate_act, cell_act, h_prev, c_prev)


@register_layer
@dataclass
class GravesBidirectionalLSTM(GravesLSTM):
    """Bidirectional Graves LSTM; output = fwd + bwd SUM (reference
    `GravesBidirectionalLSTM.java:222` `fwdOutput.addi(backOutput)`)."""

    TYPE = "graves_bidirectional_lstm"

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        kf, kb = jax.random.split(key)
        f = GravesLSTM.init_params(self, kf, it, dtype)
        bwd = GravesLSTM.init_params(self, kb, it, dtype)
        out = {f"{k}_f": v for k, v in f.items()}
        out.update({f"{k}_b": v for k, v in bwd.items()})
        return out

    def param_flags(self, name):
        base = name[:-2]  # strip _f/_b
        is_bias = base == "b"
        return {"is_bias": is_bias, "regularizable": base in ("W", "RW")}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        gate_act, cell_act = self._acts()
        pf = (params["pI_f"], params["pF_f"], params["pO_f"])
        pb = (params["pI_b"], params["pF_b"], params["pO_b"])
        gk, ck = self._act_kinds()
        out_f, _ = lstm_forward(x, params["W_f"], params["RW_f"], params["b_f"],
                                pf, gate_act, cell_act, mask=mask,
                                gate_is_sigmoid=gk, cell_is_tanh=ck)
        out_b, _ = lstm_forward(x, params["W_b"], params["RW_b"], params["b_b"],
                                pb, gate_act, cell_act, mask=mask, reverse=True,
                                gate_is_sigmoid=gk, cell_is_tanh=ck)
        return out_f + out_b, state


@register_layer
@dataclass
class SelfAttention(FeedForwardLayer):
    """Multi-head self-attention over a sequence (B, T, n_in) → (B, T, n_out).

    No counterpart in the reference (its sequence toolbox is LSTM-only,
    `SURVEY.md` §5 long-context note); included because long-context is
    first-class in this build. Math is `ops/attention.py`: full softmax
    attention for short sequences, flash-style blockwise (O(T) memory) when
    T > block_size. Sequence-parallel attention over a sharded time axis is
    a separate, manual API — `parallel/sequence.py` `ring_attention` /
    `ulysses_attention` (same online-softmax accumulator); this layer always
    computes over the full local sequence.
    """

    TYPE = "self_attention"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    # grouped-query attention: K/V head count (0 = n_heads; 1 = MQA).
    # Requires project_input (unprojected GQA has nothing to narrow).
    n_kv_heads: int = 0
    causal: bool = False
    # blockwise path kicks in beyond this length; None = always full attention
    block_size: Optional[int] = 1024
    project_input: bool = True

    def __post_init__(self):
        if not self.project_input and self.n_out not in (0, self.n_in):
            raise ValueError(
                f"project_input=False requires n_out == n_in (or 0); got "
                f"n_in={self.n_in}, n_out={self.n_out}")
        qkv = self.n_in if not self.project_input else (self.n_out or self.n_in)
        if qkv % self.n_heads != 0:
            raise ValueError(
                f"attention width {qkv} not divisible by n_heads={self.n_heads}")
        if self.n_kv_heads:
            if self.n_kv_heads < 0:
                raise ValueError(f"n_kv_heads must be >= 0, got "
                                 f"{self.n_kv_heads}")
            if not self.project_input:
                raise ValueError("n_kv_heads requires project_input=True")
            if self.n_heads % self.n_kv_heads:
                raise ValueError(
                    f"n_heads {self.n_heads} not divisible by n_kv_heads "
                    f"{self.n_kv_heads}")

    @property
    def _width(self) -> int:
        return self.n_out or self.n_in

    @property
    def _kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length if isinstance(it, InputTypeRecurrent) else -1
        return InputType.recurrent(self._width, t)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        w = self._width
        kvw = self._kv_heads * (w // self.n_heads)
        kq, kk, kv, ko = jax.random.split(key, 4)
        p = {}
        if self.project_input:
            for name, kk_, cols in (("Wq", kq, w), ("Wk", kk, kvw),
                                    ("Wv", kv, kvw)):
                p[name] = self._winit(kk_, (self.n_in, cols), self.n_in,
                                      cols, dtype)
            p["bq"] = jnp.zeros((w,), dtype)
            p["bk"] = jnp.zeros((kvw,), dtype)
            p["bv"] = jnp.zeros((kvw,), dtype)
        p["Wo"] = self._winit(ko, (w, w), w, w, dtype)
        p["bo"] = jnp.zeros((w,), dtype)
        return p

    def param_flags(self, name):
        is_bias = name.startswith("b")
        return {"is_bias": is_bias, "regularizable": not is_bias}

    def _heads(self, x, n_heads=None):
        B, T, _ = x.shape
        return x.reshape(B, T, n_heads or self.n_heads, -1)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.attention import multi_head_attention

        x = self._maybe_dropout(x, train, rng)
        if self.project_input:
            Hkv = self._kv_heads
            q = self._heads(x @ params["Wq"] + params["bq"])
            # GQA K/V stay at Hkv heads: the full-attention path
            # contracts them as a broadcast/grouped einsum (no
            # materialized repeat); kernel paths widen inside the
            # dispatch (multi_head_attention)
            k = self._heads(x @ params["Wk"] + params["bk"], Hkv)
            v = self._heads(x @ params["Wv"] + params["bv"], Hkv)
        else:
            q = k = v = self._heads(x)
        out = multi_head_attention(q, k, v, causal=self.causal, key_mask=mask,
                                   block_size=self.block_size)
        B, T = out.shape[:2]
        out = out.reshape(B, T, -1) @ params["Wo"] + params["bo"]
        return self._act()(out), state


# ---------------------------------------------------------------------------
# embedding / dropout / activation / pooling


@register_layer
@dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Embedding lookup (reference `nn/conf/layers/EmbeddingLayer.java`, impl
    `nn/layers/feedforward/embedding/EmbeddingLayer.java`: one-hot×W as a
    gather). Input: int indices (B,) or (B,1)."""

    TYPE = "embedding"
    input_kind = "ff"
    # consumes int ids: exempt from mixed-precision feature casts (bf16
    # cannot represent odd integers above 256)
    integer_input = True
    n_in: int = 0
    n_out: int = 0

    def output_type(self, it: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        W = self._winit(key, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)
        b = jnp.full((self.n_out,), self.bias_init or 0.0, dtype)
        return {"W": W, "b": b}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        y = params["W"][idx] + params["b"]
        return self._act()(y), state


@register_layer
@dataclass
class DropoutLayer(Layer):
    """Standalone dropout (reference `nn/conf/layers/DropoutLayer.java`)."""

    TYPE = "dropout_layer"

    @property
    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        return it

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._maybe_dropout(x, train, rng), state


@register_layer
@dataclass
class ActivationLayer(Layer):
    """Standalone activation (reference `nn/conf/layers/ActivationLayer.java`)."""

    TYPE = "activation_layer"

    @property
    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        return it

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._act()(x), state


@register_layer
@dataclass
class GlobalPoolingLayer(Layer):
    """Global pooling over time (RNN) or space (CNN) with mask support
    (reference `nn/conf/layers/GlobalPoolingLayer.java`)."""

    TYPE = "global_pooling"
    pooling_type: PoolingType = PoolingType.MAX
    pnorm: int = 2

    @property
    def has_params(self):
        return False

    def output_type(self, it: InputType) -> InputType:
        if isinstance(it, InputTypeRecurrent):
            return InputType.feed_forward(it.size)
        if isinstance(it, InputTypeConvolutional):
            return InputType.feed_forward(it.channels)
        return it

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        if x.ndim == 3:  # (B, T, F), mask (B, T)
            axes = (1,)
            m = None if mask is None else mask[:, :, None]
        elif x.ndim == 4:  # (B, H, W, C)
            axes, m = (1, 2), None
        else:
            raise ValueError(f"global pooling needs 3d/4d input, got {x.shape}")
        pt = self.pooling_type
        if pt == PoolingType.MAX:
            xm = x if m is None else jnp.where(m > 0, x, -jnp.inf)
            return jnp.max(xm, axis=axes), state
        if pt == PoolingType.SUM:
            xs = x if m is None else x * m
            return jnp.sum(xs, axis=axes), state
        if pt == PoolingType.AVG:
            if m is None:
                return jnp.mean(x, axis=axes), state
            return jnp.sum(x * m, axis=axes) / jnp.clip(jnp.sum(m, axis=axes), 1.0, None), state
        if pt == PoolingType.PNORM:
            p = float(self.pnorm)
            xs = jnp.abs(x) ** p if m is None else (jnp.abs(x) * m) ** p
            return jnp.sum(xs, axis=axes) ** (1.0 / p), state
        raise ValueError(pt)


# ---------------------------------------------------------------------------
# autoencoder


@register_layer
@dataclass
class AutoEncoder(FeedForwardLayer):
    """Denoising autoencoder (reference `nn/conf/layers/AutoEncoder.java`,
    impl `nn/layers/feedforward/autoencoder/AutoEncoder.java`): encode in
    forward; layerwise pretraining reconstructs through W^T with corruption."""

    TYPE = "autoencoder"
    input_kind = "ff"
    n_in: int = 0
    n_out: int = 0
    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: LossFunction = LossFunction.MSE

    def output_type(self, it: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        W = self._winit(key, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)
        return {"W": W, "b": jnp.zeros((self.n_out,), dtype),
                "vb": jnp.zeros((self.n_in,), dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        return self._act()(x @ params["W"] + params["b"]), state

    def pretrain_loss(self, params, x, rng):
        """Denoising reconstruction loss for unsupervised layerwise pretrain
        (reference `AutoEncoder.computeGradientAndScore` + `getCorruptedInput`)."""
        if self.corruption_level > 0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level, x.shape)
            xc = jnp.where(keep, x, 0.0)
        else:
            xc = x
        act = self._act()
        h = act(xc @ params["W"] + params["b"])
        recon = act(h @ params["W"].T + params["vb"])
        from deeplearning4j_tpu.ops.losses import loss_fn

        return loss_fn(self.loss)(x, recon)


# ---------------------------------------------------------------------------
# RBM


class HiddenUnit(str, enum.Enum):
    BINARY = "binary"
    GAUSSIAN = "gaussian"
    RECTIFIED = "rectified"
    SOFTMAX = "softmax"


class VisibleUnit(str, enum.Enum):
    BINARY = "binary"
    GAUSSIAN = "gaussian"
    SOFTMAX = "softmax"
    LINEAR = "linear"


@register_layer
@dataclass
class RBM(FeedForwardLayer):
    """Restricted Boltzmann machine (reference `nn/conf/layers/RBM.java` +
    impl `nn/layers/feedforward/rbm/RBM.java`, 501 LoC contrastive
    divergence).

    TPU-native CD-k: instead of the reference's explicit positive/negative
    phase gradient assembly, the CD update is expressed as the gradient of
    the free-energy surrogate  F(v_data) − F(stop_gradient(v_model))  where
    v_model comes from a k-step Gibbs chain — `jax.grad` of that scalar IS
    the CD-k gradient, so the whole pretrain step fuses into one XLA program.
    """

    TYPE = "rbm"
    input_kind = "ff"
    n_in: int = 0
    n_out: int = 0
    hidden_unit: HiddenUnit = HiddenUnit.BINARY
    visible_unit: VisibleUnit = VisibleUnit.BINARY
    k: int = 1
    sparsity: float = 0.0

    def output_type(self, it: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        W = self._winit(key, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)
        return {"W": W, "b": jnp.zeros((self.n_out,), dtype),
                "vb": jnp.zeros((self.n_in,), dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        act = self._act() if self.activation is not None else activation_fn(Activation.SIGMOID)
        return act(x @ params["W"] + params["b"]), state

    # -- Gibbs machinery ----------------------------------------------------
    def _h_given_v(self, params, v, key):
        pre = v @ params["W"] + params["b"]
        if self.hidden_unit == HiddenUnit.BINARY:
            mean = jax.nn.sigmoid(pre)
            sample = jax.random.bernoulli(key, mean).astype(v.dtype) if key is not None else mean
        elif self.hidden_unit == HiddenUnit.RECTIFIED:
            mean = jax.nn.relu(pre)
            if key is not None:  # NReLU: relu(pre + N(0, sigmoid(pre)))
                noise = jax.random.normal(key, pre.shape, v.dtype) * jnp.sqrt(jax.nn.sigmoid(pre))
                sample = jax.nn.relu(pre + noise)
            else:
                sample = mean
        elif self.hidden_unit == HiddenUnit.GAUSSIAN:
            mean = pre
            sample = pre + (jax.random.normal(key, pre.shape, v.dtype) if key is not None else 0.0)
        elif self.hidden_unit == HiddenUnit.SOFTMAX:
            mean = jax.nn.softmax(pre, axis=-1)
            sample = mean
        else:
            raise ValueError(self.hidden_unit)
        return mean, sample

    def _v_given_h(self, params, h, key):
        pre = h @ params["W"].T + params["vb"]
        if self.visible_unit == VisibleUnit.BINARY:
            mean = jax.nn.sigmoid(pre)
            sample = jax.random.bernoulli(key, mean).astype(h.dtype) if key is not None else mean
        elif self.visible_unit == VisibleUnit.GAUSSIAN:
            mean = pre
            sample = pre + (jax.random.normal(key, pre.shape, h.dtype) if key is not None else 0.0)
        elif self.visible_unit == VisibleUnit.SOFTMAX:
            mean = jax.nn.softmax(pre, axis=-1)
            sample = mean
        elif self.visible_unit == VisibleUnit.LINEAR:
            mean = sample = pre
        else:
            raise ValueError(self.visible_unit)
        return mean, sample

    def free_energy(self, params, v):
        """F(v), per unit type. Hidden term = log Σ_h exp(h·pre − E_h):
        BINARY Σ softplus(pre); GAUSSIAN Σ pre²/2; RECTIFIED Σ softplus(pre)
        (standard NReLU approximation); SOFTMAX logsumexp(pre). Visible term:
        BINARY/SOFTMAX −v·vb; GAUSSIAN/LINEAR ½Σ(v−vb)²."""
        pre = v @ params["W"] + params["b"]
        if self.hidden_unit == HiddenUnit.GAUSSIAN:
            hidden_term = 0.5 * jnp.sum(pre ** 2, axis=-1)
        elif self.hidden_unit == HiddenUnit.SOFTMAX:
            hidden_term = jax.scipy.special.logsumexp(pre, axis=-1)
        else:  # BINARY, RECTIFIED
            hidden_term = jnp.sum(jax.nn.softplus(pre), axis=-1)
        if self.visible_unit in (VisibleUnit.GAUSSIAN, VisibleUnit.LINEAR):
            vis_term = 0.5 * jnp.sum((v - params["vb"]) ** 2, axis=-1)
            return vis_term - hidden_term
        return -(v @ params["vb"]) - hidden_term

    def gibbs_chain(self, params, v0, rng, k: int):
        if k < 1:
            raise ValueError(f"RBM contrastive divergence needs k >= 1, got k={k}")
        v = v0
        for i in range(k):
            kh, kv, rng = (jax.random.split(rng, 3) if rng is not None
                           else (None, None, None))
            _, h = self._h_given_v(params, v, kh)
            v_mean, v = self._v_given_h(params, h, kv)
        # end chain on the mean-field reconstruction (lower variance)
        return v_mean

    def pretrain_loss(self, params, x, rng):
        vk = jax.lax.stop_gradient(self.gibbs_chain(params, x, rng, self.k))
        cd = jnp.mean(self.free_energy(params, x) - self.free_energy(params, vk))
        if self.sparsity > 0:
            h_mean, _ = self._h_given_v(params, x, None)
            cd = cd + self.sparsity * jnp.mean((jnp.mean(h_mean, axis=0) - self.sparsity) ** 2)
        return cd

    def reconstruction_error(self, params, x, rng=None):
        """Cross-entropy reconstruction error (the reference's reported RBM
        score)."""
        _, h = self._h_given_v(params, x, None)
        v_mean, _ = self._v_given_h(params, h, None)
        v_mean = jnp.clip(v_mean, 1e-7, 1 - 1e-7)
        if self.visible_unit == VisibleUnit.BINARY:
            return float(-jnp.mean(jnp.sum(
                x * jnp.log(v_mean) + (1 - x) * jnp.log(1 - v_mean), axis=-1)))
        return float(jnp.mean(jnp.sum((x - v_mean) ** 2, axis=-1)))


_FIELD_DECODERS["hidden_unit"] = HiddenUnit
_FIELD_DECODERS["visible_unit"] = VisibleUnit


@register_layer
@dataclass
class LayerNormalization(FeedForwardLayer):
    """Layer normalization over the feature axis.

    No counterpart in the reference (its only normalization is batch norm,
    `nn/conf/layers/BatchNormalization.java`); required by the transformer
    tier. Statistics are computed in promoted >= f32 precision (same
    rationale as BatchNormalization under bf16 mixed precision).
    `has_bias=False`: a gain alone, no `beta` leaf (Cohere's)."""

    TYPE = "layer_norm"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    eps: float = 1e-5
    has_bias: bool = True

    def __post_init__(self):
        if self.n_out and self.n_in and self.n_out != self.n_in:
            raise ValueError("LayerNormalization keeps width: n_in == n_out")

    def output_type(self, it: InputType) -> InputType:
        return it

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        nf = self.n_out or self.n_in or it.size
        p = {"gamma": jnp.ones((nf,), dtype)}
        if self.has_bias:
            p["beta"] = jnp.zeros((nf,), dtype)
        return p

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return layer_norm(x, params["gamma"], params.get("beta"),
                          self.eps), state


def layer_norm(x, gamma, beta, eps=1e-5):
    # NOTE (r3): a one-pass E[x^2]-mean^2 variant with bf16 application
    # (the BatchNormalization treatment) was measured at NO gain here on
    # either GPT bench config — XLA already fuses the f32 upcast into the
    # row-wise LN computation, so the straightforward form stays.
    stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
    xs = x.astype(stat_dtype)
    mean = jnp.mean(xs, axis=-1, keepdims=True)
    var = jnp.var(xs, axis=-1, keepdims=True)
    xhat = (xs - mean) / jnp.sqrt(var + eps)
    out = xhat * gamma.astype(stat_dtype)
    if beta is not None:
        out = out + beta.astype(stat_dtype)
    return out.astype(x.dtype)


def rms_norm(x, w, eps: float = 1e-5):
    """x / sqrt(mean(x^2) + eps) * w, statistics in float32."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


@register_layer
@dataclass
class RMSNormalization(FeedForwardLayer):
    """Root-mean-square normalization over the feature axis, one gain
    and no bias (`rms_norm`, which the composed block's norm kind
    shares)."""

    TYPE = "rms_norm"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    eps: float = 1e-5

    def output_type(self, it: InputType) -> InputType:
        return it

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        return {"gamma": jnp.ones((self.n_out or self.n_in or it.size,),
                                  dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], self.eps), state


@register_layer
@dataclass
class TokenEmbedding(FeedForwardLayer):
    """Token + learned positional embedding: (B, T) int ids → (B, T, D).

    The sequence-model entry point (reference has no transformer tier; its
    EmbeddingLayer handles one id per example)."""

    TYPE = "token_embedding"
    input_kind = "rnn"
    integer_input = True  # int ids: exempt from compute-dtype casts
    n_in: int = 0          # vocabulary size
    n_out: int = 0         # d_model
    max_length: int = 512
    # False: tokens only — for RoPE models, where position lives in the
    # attention rotation and a learned absolute table would fight it
    positional: bool = True
    # the embedding is multiplied by this on the way out (Granite's
    # `embedding_multiplier`); the table itself stays unscaled, so a
    # tied output head reads the same matrix
    multiplier: float = 1.0

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length if isinstance(it, InputTypeRecurrent) else -1
        return InputType.recurrent(self.n_out, t)

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        k1, k2 = jax.random.split(key)
        tok = self._winit(k1, (self.n_in, self.n_out), self.n_in, self.n_out,
                          dtype)
        if not self.positional:
            return {"W": tok}
        pos = 0.02 * jax.random.normal(k2, (self.max_length, self.n_out),
                                       dtype)
        return {"W": tok, "P": pos}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3:  # (B, T, 1) convenience
            idx = idx[..., 0]
        T = idx.shape[1]
        if self.positional and T > self.max_length:
            # only the learned table bounds length; positional=False
            # (RoPE models) extrapolates freely — position is relative
            raise ValueError(f"sequence length {T} exceeds max_length "
                             f"{self.max_length}")
        y = params["W"][idx]
        if self.positional:
            y = y + params["P"][:T]
        y = self._maybe_dropout(self.scaled(y), train, rng)
        return y, state

    def scaled(self, y):
        """`y * multiplier`: for every path that looks the table up
        itself (this forward, the decode engine's programs)."""
        if self.multiplier == 1.0:
            return y
        return y * jnp.asarray(self.multiplier, y.dtype)

    def param_flags(self, name):
        # positional table: neither a bias nor weight-decayed
        if name == "P":
            return {"is_bias": False, "regularizable": False}
        return super().param_flags(name)


@register_layer
@dataclass
class TransformerBlock(FeedForwardLayer):
    """Pre-LN transformer block: x + MHA(LN(x)), then x + FFN(LN(x)).

    Self-contained (attention + FFN + both norms in one layer) so a GPT is
    a plain MultiLayerNetwork stack; the attention math dispatches through
    `ops/attention.py` (pallas flash kernel for long unmasked sequences)."""

    TYPE = "transformer_block"
    input_kind = "rnn"
    n_in: int = 0          # d_model
    n_out: int = 0
    n_heads: int = 4
    # grouped-query attention: number of K/V heads (0 = n_heads, i.e.
    # full MHA; 1 = MQA). Each KV head serves n_heads/n_kv_heads query
    # heads. Training repeats KV heads to full width before the attention
    # kernels (flash/ring/Ulysses paths unchanged); the payoff is DECODE,
    # where the KV cache — the bandwidth bound of autoregressive
    # generation — shrinks by the group factor (models/transformer.py
    # caches only the n_kv_heads heads).
    n_kv_heads: int = 0
    # rotary position embeddings (relative-position attention; pair with
    # TokenEmbedding(positional=False) — gpt_configuration(rope=True)
    # wires both). Keys rotate at their absolute position, so the q.k
    # product depends only on relative distance; needs even head_dim.
    rope: bool = False
    rope_base: float = 10000.0
    ffn_mult: int = 4
    # "gelu": h = gelu(x W1 + b1) W2 + b2 (the historical default).
    # "swiglu": h = (silu(x W1) * (x W3)) W2 — gated linear unit with a
    # third projection; with rope + n_kv_heads this is the llama-style
    # decoder block. (Dense FFN only; the Switch-MoE expert FFN keeps
    # gelu.)
    ffn_activation: str = "gelu"
    causal: bool = True
    block_size: Optional[int] = 1024
    eps: float = 1e-5
    # > 0: replace the dense FFN with a Switch MoE of this many experts
    # (load-balancing aux loss via ops/aux_loss)
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # rematerialize the block in the backward pass (jax.checkpoint):
    # trades ~1/3 extra FLOPs for O(1) residual memory per block — the
    # long-context/large-batch enabler. Dense blocks only (the MoE aux-loss
    # side channel must not be recomputed).
    remat: bool = False

    def __post_init__(self):
        d = self.n_out or self.n_in
        if d and d % self.n_heads:
            raise ValueError(f"d_model {d} not divisible by n_heads "
                             f"{self.n_heads}")
        if self.n_in and self.n_out and self.n_in != self.n_out:
            raise ValueError("TransformerBlock keeps width: n_in == n_out")
        if self.n_kv_heads:
            if self.n_kv_heads < 0:
                raise ValueError(f"n_kv_heads must be >= 0, got "
                                 f"{self.n_kv_heads}")
            if self.n_heads % self.n_kv_heads:
                raise ValueError(
                    f"n_heads {self.n_heads} not divisible by n_kv_heads "
                    f"{self.n_kv_heads} (each KV head serves an equal "
                    "group of query heads)")
        if self.rope and d and (d // self.n_heads) % 2:
            raise ValueError(
                f"RoPE rotates feature PAIRS: head_dim {d // self.n_heads} "
                "must be even")
        if self.ffn_activation not in ("gelu", "swiglu"):
            raise ValueError(f"unknown ffn_activation "
                             f"{self.ffn_activation!r}: gelu | swiglu")
        if self.ffn_activation == "swiglu" and self.moe_experts > 0:
            raise ValueError("swiglu applies to the dense FFN only; the "
                             "Switch-MoE expert FFN keeps gelu")

    @property
    def _d(self) -> int:
        return self.n_out or self.n_in

    @property
    def _kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def output_type(self, it: InputType) -> InputType:
        return it

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        d = self._d
        h = d * self.ffn_mult
        # fixed split count: the router key is derived by fold_in so that
        # dense (moe_experts=0) blocks keep bit-identical seeded init
        # whether or not the MoE branch exists in this version
        ks = jax.random.split(key, 4)
        mk = lambda k, shape, fi, fo: self._winit(k, shape, fi, fo, dtype)
        # q takes d columns; k and v take kvw = n_kv_heads * head_dim each
        # (== d for full MHA, where this reduces to the historical (d, 3d)
        # fused projection with bit-identical seeded init)
        kvw = self._kv_heads * (d // self.n_heads)
        w3 = d + 2 * kvw
        params = {
            "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "Wqkv": mk(ks[0], (d, w3), d, w3),
            "bqkv": jnp.zeros((w3,), dtype),
            "Wo": mk(ks[1], (d, d), d, d), "bo": jnp.zeros((d,), dtype),
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
        }
        E = self.moe_experts
        if E > 0:  # sparse-expert FFN (Switch)
            params.update({
                "router": mk(jax.random.fold_in(key, 4), (d, E), d, E),
                "W1": mk(ks[2], (E, d, h), d, h),
                "b1": jnp.zeros((E, h), dtype),
                "W2": mk(ks[3], (E, h, d), h, d),
                "b2": jnp.zeros((E, d), dtype),
            })
        elif self.ffn_activation == "swiglu":
            params.update({
                "W1": mk(ks[2], (d, h), d, h),
                "W3": mk(jax.random.fold_in(key, 5), (d, h), d, h),
                "W2": mk(ks[3], (h, d), h, d), "b2": jnp.zeros((d,), dtype),
            })
        else:
            params.update({
                "W1": mk(ks[2], (d, h), d, h), "b1": jnp.zeros((h,), dtype),
                "W2": mk(ks[3], (h, d), h, d), "b2": jnp.zeros((d,), dtype),
            })
        return params

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        if self.remat and self.moe_experts == 0 and train:
            import functools

            body = functools.partial(self._block_body, train=train)
            out = jax.checkpoint(body)(params, x, rng, mask)
            return out, state
        return self._block_body(params, x, rng, mask, train=train), state

    def _block_body(self, params, x, rng, mask, *, train):
        from deeplearning4j_tpu.ops.attention import multi_head_attention

        B, T, d = x.shape
        H = self.n_heads
        Hkv = self._kv_heads
        hd = d // H
        # the scopes name the block's parts in a profiler capture, under
        # the same names as the serving path (models/transformer.py)
        with jax.named_scope("ln1"):
            h1 = layer_norm(x, params["ln1_g"], params["ln1_b"], self.eps)
        with jax.named_scope("attn.qkv"):
            qkv = h1 @ params["Wqkv"] + params["bqkv"]
            kvw = Hkv * hd
            q = qkv[..., :d].reshape(B, T, H, hd)
            k = qkv[..., d:d + kvw].reshape(B, T, Hkv, hd)
            v = qkv[..., d + kvw:].reshape(B, T, Hkv, hd)
            if self.rope:
                from deeplearning4j_tpu.ops.rope import (
                    rope_angles,
                    rope_rotate,
                )

                cos, sin = rope_angles(jnp.arange(T), hd, self.rope_base)
                q = rope_rotate(q, cos, sin)
                k = rope_rotate(k, cos, sin)
        # GQA: query head j attends through KV head j // (H // Hkv).
        # K/V go to the dispatch UN-repeated (Hkv heads): the
        # full-attention path groups them as a broadcast einsum —
        # bit-identical per-head dots without copying each KV element
        # H/Hkv× through HBM — and the kernel paths (flash/blockwise/
        # ring) widen inside multi_head_attention
        with jax.named_scope("attn.core"):
            att = multi_head_attention(q, k, v, causal=self.causal,
                                       key_mask=mask,
                                       block_size=self.block_size)
        with jax.named_scope("attn.out"):
            att = att.reshape(B, T, d) @ params["Wo"] + params["bo"]
            att = self._maybe_dropout(att, train, rng)
            x = x + att
        with jax.named_scope("ln2"):
            h2 = layer_norm(x, params["ln2_g"], params["ln2_b"], self.eps)
        if self.moe_experts > 0:
            from deeplearning4j_tpu.parallel.experts import switch_ffn

            tokens = h2.reshape(-1, d)
            token_mask = mask.reshape(-1) if mask is not None else None
            # passthrough="zero": the block adds its own residual below, so
            # dropped (overflow/masked) tokens must contribute 0 to the FFN
            # term — identity would double-add ln2(x)
            ffn = switch_ffn(params, tokens, act=jax.nn.gelu,  # block's FFN
                             capacity_factor=self.moe_capacity_factor,
                             aux_weight=self.moe_aux_weight,
                             token_mask=token_mask,
                             train=train,
                             passthrough="zero").reshape(B, T, d)
        elif self.ffn_activation == "swiglu":
            with jax.named_scope("mlp.up"):
                gate, up = h2 @ params["W1"], h2 @ params["W3"]
            with jax.named_scope("mlp.act"):
                act = jax.nn.silu(gate) * up
            with jax.named_scope("mlp.down"):
                ffn = act @ params["W2"] + params["b2"]
        else:
            with jax.named_scope("mlp.up"):
                up = h2 @ params["W1"] + params["b1"]
            with jax.named_scope("mlp.act"):
                act = jax.nn.gelu(up)
            with jax.named_scope("mlp.down"):
                ffn = act @ params["W2"] + params["b2"]
        ffn = self._maybe_dropout(
            ffn, train, None if rng is None else jax.random.fold_in(rng, 1))
        return x + ffn

    def param_flags(self, name):
        is_bias = name.startswith("b") or name.endswith("_b")
        norm_scale = name.endswith("_g")
        return {"is_bias": is_bias,
                "regularizable": not is_bias and not norm_scale}


@register_layer
@dataclass
class MoELayer(FeedForwardLayer):
    """Switch-style top-1 mixture-of-experts FFN: (B, T, D) or (B, D) →
    same shape; router picks one expert per token, overflow passes through.

    No counterpart in the reference. Math is
    `parallel/experts.moe_apply_reference` (global-capacity semantics); the
    load-balancing loss is contributed via `ops/aux_loss.add_aux_loss`, so
    it only takes effect during training (`_loss_pure` collects it).

    Expert-PARALLEL execution is a network feature: set
    `expert_axis="expert"` and train through `ParallelWrapper` over a mesh
    with that axis (sized n_experts). The wrapper shards the stacked
    expert weights over the axis and this layer routes tokens through
    `moe_apply`'s all_to_all inside the compiled step; without a wrapper
    (or off-mesh) the layer falls back to the replicated path, so the same
    config runs anywhere."""

    TYPE = "moe"
    input_kind = "rnn"
    n_in: int = 0
    n_out: int = 0
    n_experts: int = 4
    hidden_mult: int = 4
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    expert_axis: Optional[str] = None
    # expert hidden activation; a dedicated field (not `activation`) so the
    # builder's global activation default (sigmoid) cannot silently change
    # the expert nonlinearity — set explicitly to override
    expert_activation: Activation = Activation.RELU

    def __post_init__(self):
        if self.n_in and self.n_out and self.n_in != self.n_out:
            raise ValueError("MoELayer keeps width: n_in == n_out")

    @property
    def _d(self) -> int:
        return self.n_out or self.n_in

    def output_type(self, it: InputType) -> InputType:
        return it

    def init_params(self, key, it, dtype=jnp.float32) -> Params:
        d = self._d
        h = d * self.hidden_mult
        E = self.n_experts
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "router": self._winit(k1, (d, E), d, E, dtype),
            "W1": self._winit(k2, (E, d, h), d, h, dtype),
            "b1": jnp.zeros((E, h), dtype),
            "W2": self._winit(k3, (E, h, d), h, d, dtype),
            "b2": jnp.zeros((E, d), dtype),
        }

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.parallel.experts import (
            current_expert_mesh,
            switch_ffn,
            switch_ffn_sharded,
        )

        x = self._maybe_dropout(x, train, rng)
        shape = x.shape
        tokens = x.reshape(-1, shape[-1])
        # padding tokens must not route, consume capacity, or weight the
        # load-balancing loss
        token_mask = (mask.reshape(-1) if mask is not None
                      and len(shape) == 3 else None)
        act = activation_fn(self.expert_activation)
        scope = current_expert_mesh()
        if (self.expert_axis and scope is not None
                and self.expert_axis in scope[0].shape):
            if token_mask is not None:
                raise NotImplementedError(
                    "masked sequences are not supported on the expert-"
                    "parallel path yet — train unmasked batches, or drop "
                    "expert_axis to use the replicated path")
            mesh, data_axis = scope
            y = switch_ffn_sharded(
                params, tokens, mesh, axis_name=self.expert_axis,
                data_axis=data_axis, act=act,
                capacity_factor=self.capacity_factor,
                aux_weight=self.aux_loss_weight, train=train)
            return y.reshape(shape), state
        y = switch_ffn(params, tokens, act=act,
                       capacity_factor=self.capacity_factor,
                       aux_weight=self.aux_loss_weight,
                       token_mask=token_mask, train=train)
        return y.reshape(shape), state

    def param_flags(self, name):
        is_bias = name.startswith("b")
        return {"is_bias": is_bias, "regularizable": not is_bias}
