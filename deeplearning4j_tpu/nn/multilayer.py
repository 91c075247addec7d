"""MultiLayerNetwork: sequential network container + training loop.

Reference: `deeplearning4j-nn/.../nn/multilayer/MultiLayerNetwork.java:80` —
`init():386`, `fit(DataSetIterator):978`, `backprop():1049`,
`doTruncatedBPTT:1140`, `output:1540`, `rnnTimeStep:2196`, `evaluate:2365` —
plus the Solver/StochasticGradientDescent loop it drives
(`optimize/solvers/StochasticGradientDescent.java:51-72`).

TPU-first design decision (SURVEY §7.3): where the reference runs a Java
training loop issuing one JNI op per ND4J call (per-layer activate →
per-layer backpropGradient → updater → step), here the ENTIRE
fwd+bwd+updater+apply iteration is traced once into a single XLA computation
with donated parameter/optimizer buffers, so params update in-place in TPU
HBM and the host loop only feeds batches and reads back the scalar score.

Parameter view semantics: the reference exposes a flat parameter vector with
per-layer views (`init():386`, `initGradientsView():475`) that optimizers and
averaging mutate in place. The TPU equivalent keeps params as a pytree (the
sharding/collective-friendly representation) and provides
`params()`/`set_params()` flat-vector conversion via `ravel_pytree` for the
serialization/averaging/gradient-check surfaces that need the flat view.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf.inputs import (
    InputType,
    InputTypeConvolutionalFlat,
    InputTypeRecurrent,
)
from deeplearning4j_tpu.nn.conf.layers import (
    GravesLSTM,
    Layer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.conf.neural_net_configuration import MultiLayerConfiguration
from deeplearning4j_tpu.nn.updater import (
    apply_layer_update,
    init_updater_state,
)

Params = List[Dict[str, jnp.ndarray]]
LState = List[Dict[str, jnp.ndarray]]


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration, dtype=jnp.float32,
                 compute_dtype=None):
        """`compute_dtype=jnp.bfloat16` enables mixed precision: parameters
        and optimizer state stay in `dtype` (f32 — update math and Adam
        moments keep full precision), while the forward/backward compute
        runs in bf16, the MXU's native feed width. Gradients come back f32
        (jax.grad of an f32->bf16 cast accumulates in f32); bf16's f32-sized
        exponent makes loss scaling unnecessary."""
        self.conf = conf
        self.dtype = dtype
        self.compute_dtype = compute_dtype
        self.layers: List[Layer] = conf.layers
        self._params: Optional[Params] = None
        self._upd_state = None
        self._layer_state: Optional[LState] = None
        self._unravel: Optional[Callable] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._score: Optional[Any] = None
        self._rnn_state: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]] = {}
        self._it_device: Optional[jnp.ndarray] = None
        self._jit_train = None
        self._jit_scan = None
        self._jit_output = None
        self._jit_rnn_step = None
        self._rnn_pos = 0
        self._normalizer = None
        self._sentinel = None
        self._input_types = self._resolve_input_types()

    # ------------------------------------------------------- normalization
    def set_normalizer(self, normalizer) -> None:
        """Attach a `DataNormalization` whose feature transform is COMPILED
        INTO the step/output functions (device-side normalization). The
        reference applies normalizers host-side between iterator and net
        (`RecordReaderDataSetIterator.setPreProcessor`); here the transform
        runs on-chip so iterators can ship raw compact dtypes (e.g. uint8
        pixels) over the host link and XLA fuses the scaling into the first
        layer. Also what `ModelSerializer.write_model(..., normalizer=)`
        persists alongside the checkpoint (`normalizer.bin`)."""
        if normalizer is not None:
            normalizer.check_device_attachable()
            if getattr(self.layers[0], "integer_input", False):
                raise ValueError(
                    "cannot attach a normalizer to a network whose first "
                    "layer consumes integer token ids "
                    f"({type(self.layers[0]).__name__}): ids are never "
                    "scaled, so the normalizer would be silently ignored")
        self._normalizer = normalizer
        # traced functions embed the transform: drop compiled caches
        self._jit_train = None
        self._jit_scan = None
        self._jit_output = None
        self._jit_rnn_step = None

    def get_normalizer(self):
        return self._normalizer

    # ------------------------------------------------------ health sentinel
    def set_health_sentinel(self, sentinel) -> None:
        """Attach a `optimize.health.HealthSentinel`: the compiled train
        step gains a FUSED finite guard — it computes one global
        gradient-norm scalar (a single reduction tree over every gradient
        leaf, no per-array pulls) plus a finiteness flag, and commits the
        candidate parameters/updater/layer state only when loss and
        gradient norm are both finite. The host reads one small
        `(loss, grad_norm, ok)` vector per step (the sentinel's single
        device→host sync) and drives EWMA spike detection + the
        skip → LR-backoff → rollback escalation ladder on it. Pass None
        to detach. Not inherited by `clone()` (sentinel state is
        host-side and per-fit-loop)."""
        self._sentinel = sentinel
        # the guarded step has a different signature/graph: recompile
        self._jit_train = None
        self._jit_scan = None

    def get_health_sentinel(self):
        return self._sentinel

    def _prep_features(self, features):
        """Traced input prep: cast compact wire dtypes to the model dtype
        and apply the attached device-side normalizer (both fuse into the
        first layer's XLA computation)."""
        mode = self._feature_wire_mode()
        if mode == "sink":
            # token ids: never scaled/normalized, integral dtypes stay
            # integral (embedding take)
            return features
        if mode == "ids":
            # id-consuming transform (OneHotEncoder): hand it int32 ids —
            # a bf16 model-dtype cast first would round ids above 256 —
            # then bring the expanded rows to the model dtype
            features = self._normalizer.device_transform(
                features.astype(jnp.int32))
            return (features if features.dtype == self.dtype
                    else features.astype(self.dtype))
        if features.dtype != self.dtype:
            features = features.astype(self.dtype)
        if self._normalizer is not None:
            features = self._normalizer.device_transform(features)
        return features

    # ----------------------------------------------------------------- score
    @property
    def score_value(self) -> Optional[float]:
        """Loss of the most recent iteration (reference `Model.score()`).

        Stored as a device array by the hot training loop and converted to a
        Python float only on first read — reading the score forces a device
        sync, and doing that every step would serialize the step pipeline
        (the host could no longer dispatch ahead of the device)."""
        if self._score is None or isinstance(self._score, float):
            return self._score
        self._score = float(self._score)
        return self._score

    @score_value.setter
    def score_value(self, v) -> None:
        self._score = v if (v is None or isinstance(v, float)) else float(v)

    # ------------------------------------------------------------------ init
    def _resolve_input_types(self) -> List[InputType]:
        """Per-layer input InputType (post-preprocessor), mirroring the
        inference done at config build time."""
        it = self.conf.input_type
        if it is None:
            l0 = self.layers[0]
            n_in = getattr(l0, "n_in", 0)
            if l0.input_kind == "rnn":
                it = InputType.recurrent(n_in)
            else:
                it = InputType.feed_forward(n_in)
        out = []
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                it = self.conf.preprocessors[i].output_type(it)
            out.append(it)
            it = layer.output_type(it)
        return out

    def init(self) -> None:
        """Build parameter/updater/layer-state pytrees (reference
        `MultiLayerNetwork.init():386`)."""
        key = jax.random.PRNGKey(self.conf.seed)
        params: Params = []
        upd = []
        lstate: LState = []
        for i, layer in enumerate(self.layers):
            key, sub = jax.random.split(key)
            p = layer.init_params(sub, self._input_types[i], self.dtype) if layer.has_params else {}
            params.append(p)
            cfg = layer.updater_cfg
            upd.append({name: init_updater_state(cfg, v) for name, v in p.items()}
                       if cfg is not None else {})
            lstate.append(layer.init_state(self._input_types[i]))
        self._params = params
        self._upd_state = upd
        self._layer_state = lstate
        flat, unravel = ravel_pytree(params)
        self._unravel = unravel

    def _ensure_init(self):
        if self._params is None:
            self.init()

    # ------------------------------------------------------------- forward
    def _params_of(self, params: Params, i: int):
        """Layer i's parameters: its own, or those of the layer it is
        tied to (`TiedRnnOutputLayer.tied_to`)."""
        tied = getattr(self.layers[i], "tied_to", None)
        return params[i if tied is None else tied]

    def _forward_pure(self, params: Params, lstate: LState, x: jnp.ndarray, *,
                      train: bool, rng: Optional[jax.Array],
                      fmask: Optional[jnp.ndarray],
                      upto: Optional[int] = None) -> Tuple[jnp.ndarray, LState]:
        """Compose all layer forwards (reference `feedForwardToLayer`,
        `MultiLayerNetwork.java:694`). Pure: jit-safe."""
        n = len(self.layers) if upto is None else upto
        new_state = list(lstate)
        for i in range(n):
            layer = self.layers[i]
            lrng = None if rng is None else jax.random.fold_in(rng, i)
            if i in self.conf.preprocessors:
                x = self.conf.preprocessors[i].preprocess(x, rng=lrng,
                                                          train=train)
            mask = fmask if x.ndim == 3 else None
            # names the layer's operations in a profiler capture
            with jax.named_scope(f"L{i}.{type(layer).__name__}"):
                x, new_state[i] = layer.forward(self._params_of(params, i),
                                                lstate[i], x,
                                                train=train, rng=lrng,
                                                mask=mask)
        return x, new_state

    def _loss_pure(self, params: Params, lstate: LState, features, labels,
                   fmask, lmask, rng, train: bool = True):
        """Loss = output-layer score + L1/L2 penalties (reference
        `computeGradientAndScore` + `calcL1/calcL2` in BaseLayer)."""
        params_in, lstate_in = params, lstate
        features = self._prep_features(features)
        if self.compute_dtype is not None:
            # mixed precision: hidden-layer fwd/bwd in the compute dtype;
            # loss head, L1/L2, and carried state stay in the param dtype
            from deeplearning4j_tpu.nn.precision import tree_cast

            with jax.named_scope("cast_params"):
                params = tree_cast(params, self.compute_dtype)
            if not getattr(self.layers[0], "integer_input", False):
                # token-id inputs must NOT be cast (bf16 corrupts ids > 256);
                # in a sequential net raw features only ever feed layer 0,
                # so checking it covers every id-consuming topology here
                # (the graph variant traces reachability through vertices)
                features = features.astype(self.compute_dtype)
        from deeplearning4j_tpu.ops.aux_loss import aux_loss_scope

        with aux_loss_scope() as aux_terms:
            x, new_state = self._forward_pure(params, lstate, features,
                                              train=train, rng=rng,
                                              fmask=fmask,
                                              upto=len(self.layers) - 1)
        if self.compute_dtype is not None:
            from deeplearning4j_tpu.nn.precision import restore_dtypes

            x = x.astype(self.dtype)
            new_state = restore_dtypes(new_state, lstate_in)
        out_layer = self.layers[-1]
        out_rng = None if rng is None else jax.random.fold_in(rng, len(self.layers) - 1)
        if len(self.layers) - 1 in self.conf.preprocessors:
            x = self.conf.preprocessors[len(self.layers) - 1].preprocess(
                x, rng=out_rng, train=train)
        mask = lmask if lmask is not None else (fmask if x.ndim == 3 else None)
        with jax.named_scope("loss"):
            loss = out_layer.loss_score(
                self._params_of(params_in, len(self.layers) - 1), x, labels,
                                        train=train, rng=out_rng, mask=mask)
        loss = loss + self._reg_score(params_in)
        for term in aux_terms:  # mid-network losses (MoE load balancing)
            loss = loss + term
        return loss, new_state

    def _reg_score(self, params: Params):
        from deeplearning4j_tpu.nn.updater import regularization_score

        return regularization_score(zip(self.layers, params))

    # ---------------------------------------------------------- train step
    def train_step_fn(self):
        """The pure (un-jitted) train-step function: one fwd+bwd+update.
        Exposed so distributed wrappers can re-jit it with shardings over a
        device mesh (parallel/ParallelWrapper — the reference's
        `ParallelWrapper.java` seam, with ICI all-reduce instead of
        `Nd4j.averageAndPropagate`).

        The iteration counter is a DEVICE scalar carried (donated) through
        the step, and the dropout rng is derived from it inside the trace —
        so the host loop issues exactly one dispatch per step with no
        host->device transfers besides the batch itself, and steps pipeline
        without any synchronisation."""
        core = self._step_core()

        def step(params, upd, lstate, iteration, features, labels, fmask, lmask):
            new_params, new_upd, new_lstate, loss, _ = core(
                params, upd, lstate, iteration, features, labels, fmask,
                lmask)
            return new_params, new_upd, new_lstate, iteration + 1, loss

        return step

    def _step_core(self):
        """Shared fwd+bwd+update body behind BOTH `train_step_fn` and the
        sentinel-guarded step (`_guarded_step_fn`) — one definition, so
        guarded and unguarded runs can never drift apart in math. Also
        returns the gradients: the unguarded step discards them (they are
        already consumed by the updates, so XLA adds no extra work) and
        the guarded step folds them into its fused grad-norm scalar."""
        seed = self.conf.seed

        def core(params, upd, lstate, iteration, features, labels, fmask,
                 lmask):
            rng = jax.random.fold_in(jax.random.PRNGKey(seed), iteration)
            (loss, new_lstate), grads = jax.value_and_grad(
                self._loss_pure, has_aux=True)(params, lstate, features, labels,
                                               fmask, lmask, rng, True)
            new_params = []
            new_upd = []
            for i, layer in enumerate(self.layers):
                with jax.named_scope(f"update.L{i}"):
                    p_new, u_new = apply_layer_update(
                        layer, upd[i], params[i], grads[i], iteration)
                new_params.append(p_new)
                new_upd.append(u_new)
            return new_params, new_upd, new_lstate, loss, grads

        return core

    def _make_train_step(self):
        """Jit the train step with donated param/opt/state buffers — the ONE
        compiled XLA computation per step (in-place update in HBM). With a
        health sentinel attached the guarded variant compiles instead."""
        if self._sentinel is not None:
            return jax.jit(self._guarded_step_fn(),
                           donate_argnums=(0, 1, 2, 3))
        return jax.jit(self.train_step_fn(), donate_argnums=(0, 1, 2, 3))

    def _guarded_step_fn(self):
        """Sentinel-guarded train step: same fwd+bwd+update as
        `train_step_fn`, plus (a) a fused single-scalar global
        gradient-norm reduction, (b) an on-device finite guard that keeps
        the OLD params/updater/layer state when loss or grad-norm is
        non-finite (a poisoned batch can never overwrite good parameters
        or corrupt batch-norm running stats), and (c) a `(3,)` health
        vector output `[loss, grad_norm, ok]` the host sentinel reads in
        one sync. The iteration counter still advances on a skipped step
        (the batch was consumed; host and device clocks stay in
        lockstep). Computed in f32: a gradient whose squared-norm
        overflows f32 is treated as non-finite, which is the safe
        verdict."""
        core = self._step_core()

        def step(params, upd, lstate, iteration, features, labels, fmask,
                 lmask):
            new_params, new_upd, new_lstate, loss, grads = core(
                params, upd, lstate, iteration, features, labels, fmask,
                lmask)
            leaves = jax.tree.leaves(grads)
            gnorm_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                           for g in leaves) if leaves \
                else jnp.asarray(0.0, jnp.float32)
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm_sq)
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new, old)
            new_params = keep(new_params, params)
            new_upd = keep(new_upd, upd)
            new_lstate = keep(new_lstate, lstate)
            health = jnp.stack([loss.astype(jnp.float32),
                                jnp.sqrt(gnorm_sq),
                                ok.astype(jnp.float32)])
            return (new_params, new_upd, new_lstate, iteration + 1, loss,
                    health)

        return step

    def _make_scan_train(self):
        """K steps per dispatch: `lax.scan` of the train step over stacked
        batches (K, B, ...). The whole K-step loop is ONE XLA computation —
        one host dispatch, one (K,) loss readback — so per-dispatch host
        latency amortizes over K steps. The device-side training loop the reference
        architecture can't express (its Java loop must drive every op)."""
        step = self.train_step_fn()

        def multi(params, upd, lstate, iteration, feats, labels):
            def body(carry, batch):
                params, upd, lstate, it = carry
                f, l = batch
                params, upd, lstate, it, loss = step(
                    params, upd, lstate, it, f, l, None, None)
                return (params, upd, lstate, it), loss

            (params, upd, lstate, iteration), losses = jax.lax.scan(
                body, (params, upd, lstate, iteration), (feats, labels))
            return params, upd, lstate, iteration, losses

        return jax.jit(multi, donate_argnums=(0, 1, 2, 3))

    def _feature_wire_mode(self) -> str:
        """Wire/prep mode for the feature array — single source of truth
        consumed by BOTH the wire (`wire_asarray as_ids`) and the traced
        `_prep_features`, so the two can't drift: 'sink' (integer-id first
        layer, ids pass straight through), 'ids' (id-consuming normalizer
        expands raw int32 ids), 'float' (model-dtype cast + normalizer)."""
        if getattr(self.layers[0], "integer_input", False):
            return "sink"
        if (self._normalizer is not None
                and self._normalizer.consumes_integer_ids):
            return "ids"
        return "float"

    def _features_are_ids(self) -> bool:
        """True when the wire must never float-cast the features."""
        return self._feature_wire_mode() != "float"

    def _batch_arrays(self, ds: DataSet):
        from deeplearning4j_tpu.nn.precision import wire_asarray

        f = wire_asarray(ds.features, self.dtype, self._features_are_ids())
        # labels ride the same wire policy: sparse int class ids stay int
        # (vocab× fewer bytes than one-hot), floats widen to the model dtype
        l = wire_asarray(ds.labels, self.dtype) if ds.labels is not None else None
        fm = jnp.asarray(ds.features_mask, self.dtype) if ds.features_mask is not None else None
        lm = jnp.asarray(ds.labels_mask, self.dtype) if ds.labels_mask is not None else None
        return f, l, fm, lm

    def fit(self, data: Union[DataSet, DataSetIterator, np.ndarray],
            labels: Optional[np.ndarray] = None, epochs: int = 1,
            scan_steps: int = 1) -> None:
        """Train (reference `fit(DataSetIterator)`,
        `MultiLayerNetwork.java:978`; iterator wrapped in async prefetch at
        `:982`).

        `scan_steps=K` (K>1) runs K consecutive batches per device dispatch
        via `lax.scan` (see `_make_scan_train`) — use for small/fast models
        where host dispatch latency bounds throughput. Requires uniform
        batch shapes, no masks, and no listeners (listeners need
        per-iteration model state, which a scanned chunk never
        materializes); non-conforming batches fall back to the per-step
        path transparently."""
        self._ensure_init()
        if isinstance(data, np.ndarray) or isinstance(data, jnp.ndarray):
            data = DataSet(np.asarray(data), np.asarray(labels))
        if isinstance(data, DataSet):
            iterator: DataSetIterator = ListDataSetIterator([data])
        else:
            iterator = data
        wrapped_async = False
        if iterator.async_supported and not isinstance(iterator, AsyncDataSetIterator):
            iterator = AsyncDataSetIterator(iterator)
            wrapped_async = True

        if self._jit_train is None:
            self._jit_train = self._make_train_step()
        # (re)sync the device-side iteration counter with the host counter
        # once per fit() call, not per step
        self._it_device = jnp.asarray(self.iteration, jnp.int32)

        from deeplearning4j_tpu.nn.conf.neural_net_configuration import (
            OptimizationAlgorithm,
        )

        line_search_algo = (self.conf.global_conf.optimization_algo
                            != OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT)
        tbptt = (self.conf.tbptt_fwd_length > 0)
        scan = scan_steps > 1 and not line_search_algo and not tbptt
        if scan and self._sentinel is not None:
            # the sentinel needs per-step health scalars; a scanned chunk
            # never materializes them (and the per-step host sync the
            # sentinel forces erases scan's dispatch amortization anyway)
            import logging

            logging.getLogger("deeplearning4j_tpu").info(
                "scan_steps disabled: health sentinel attached needs "
                "per-step health checks")
            scan = False
        if scan and self.listeners:
            # per-iteration listeners observe model state; inside a scanned
            # chunk intermediate states never materialize, so a listener at
            # iteration k would snapshot end-of-chunk params (e.g. a
            # checkpoint claiming iteration k with k+3's weights)
            import logging

            logging.getLogger("deeplearning4j_tpu").info(
                "scan_steps disabled: %d listener(s) attached need "
                "per-iteration model state", len(self.listeners))
            scan = False
        try:
            for _ in range(epochs):
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_start"):
                        listener.on_epoch_start(self)
                n_batches = 0
                pending: List[DataSet] = []
                for ds in iterator:
                    n_batches += 1
                    if line_search_algo:
                        self._fit_batch_solver(ds)
                    elif tbptt and self._tbptt_applicable(ds):
                        self._fit_tbptt(ds)
                    elif scan:
                        def _sig(d):
                            # stackability signature: features AND labels
                            # shape/dtype (sparse int vs one-hot may mix in
                            # one iterator). Attribute probes only — no
                            # np.asarray, which would round-trip an
                            # on-device array through the host
                            def probe(a):
                                if hasattr(a, "shape"):
                                    return (a.shape, a.dtype)
                                a = np.asarray(a)  # plain Python sequence
                                return (a.shape, a.dtype)

                            return probe(d.features) + probe(d.labels)

                        if (ds.features_mask is not None or ds.labels_mask is not None
                                or (pending and _sig(ds) != _sig(pending[0]))):
                            self._flush_scan(pending, scan_steps)  # shape change / masks
                            pending = []
                            self._fit_batch(ds)
                            continue
                        pending.append(ds)
                        if len(pending) == scan_steps:
                            self._flush_scan(pending, scan_steps)
                            pending = []
                    else:
                        self._fit_batch(ds)
                if scan and pending:
                    self._flush_scan(pending, scan_steps)
                if n_batches == 0:
                    import logging

                    logging.getLogger("deeplearning4j_tpu").warning(
                        "fit(): iterator produced no batches this epoch — if it "
                        "wraps a generator, it may already be exhausted")
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(self)
                self.epoch += 1
        finally:
            if wrapped_async:
                # tear down the prefetch producer thread even on
                # failure (a leaked producer would race a retry
                # over the underlying iterator's cursor)
                try:
                    iterator.reset()
                except ValueError:
                    pass  # one-shot underlying cannot rewind

    def _flush_scan(self, pending: List[DataSet],
                    full: Optional[int] = None) -> None:
        """Run the accumulated uniform batches as one scanned dispatch.
        A flush SHORTER than the configured chunk (`full`) — the iterator
        tail, or a signature change mid-stream — runs per-batch through the
        already-compiled single step instead: a lax.scan is specialized on
        its length, so every distinct chunk length would trigger a fresh
        multi-second XLA compile for a one-off shape."""
        if not pending:
            return
        if len(pending) == 1 or (full is not None and len(pending) < full):
            for ds in pending:
                self._fit_batch(ds)
            return
        for ds in pending:
            self._validate_labels(ds)
        if self._jit_scan is None:
            self._jit_scan = self._make_scan_train()
        from deeplearning4j_tpu.nn.precision import stack_wire

        feats = stack_wire([ds.features for ds in pending],
                           self.dtype, self._features_are_ids())
        labels = stack_wire([ds.labels for ds in pending], self.dtype)
        if self._it_device is None:
            self._it_device = jnp.asarray(self.iteration, jnp.int32)
        (self._params, self._upd_state, self._layer_state, self._it_device,
         losses) = self._jit_scan(
            self._params, self._upd_state, self._layer_state,
            self._it_device, feats, labels)
        for i, ds in enumerate(pending):
            self._score = losses[i]  # device slice; lazy sync on read
            self.iteration += 1
            for listener in self.listeners:
                if hasattr(listener, "record_batch"):
                    listener.record_batch(ds.num_examples())
                listener.iteration_done(self, self.iteration)

    def _fit_batch(self, ds: DataSet):
        self._validate_labels(ds)
        f, l, fm, lm = self._batch_arrays(ds)
        if self._jit_train is None:  # dropped mid-fit (sentinel LR backoff)
            self._jit_train = self._make_train_step()
        if getattr(self, "_it_device", None) is None:
            self._it_device = jnp.asarray(self.iteration, jnp.int32)
        health = None
        if self._sentinel is None:
            (self._params, self._upd_state, self._layer_state,
             self._it_device, loss) = self._jit_train(
                self._params, self._upd_state, self._layer_state,
                self._it_device, f, l, fm, lm)
        else:
            (self._params, self._upd_state, self._layer_state,
             self._it_device, loss, health) = self._jit_train(
                self._params, self._upd_state, self._layer_state,
                self._it_device, f, l, fm, lm)
        self._score = loss  # device array; score_value property syncs lazily
        self._last_batch = ds  # host refs only; listeners may recompute grads
        self.iteration += 1
        if health is not None:
            # one host sync per step; may raise DivergenceRollback /
            # TrainingDivergedError (before listeners, so a checkpoint
            # listener never persists state from an escalating step)
            self._sentinel.observe(self, health)
        for listener in self.listeners:
            if hasattr(listener, "record_batch"):
                listener.record_batch(ds.num_examples())
            listener.iteration_done(self, self.iteration)

    def _fit_batch_solver(self, ds: DataSet):
        """Line-search solver path (reference `Solver.java:58-68` dispatch for
        LINE_GRADIENT_DESCENT / CONJUGATE_GRADIENT / LBFGS)."""
        from deeplearning4j_tpu.optimize.solvers import Solver

        self._validate_labels(ds)
        solver = Solver(self)
        final = solver.optimize(ds)
        self.iteration += 1
        if self._sentinel is not None:
            # the solver's host loop already materialized the score; a
            # rejected commit (non-finite candidate) reports as a skip
            self._sentinel.observe_host(
                self, final, committed=not solver.last_commit_rejected)
        for listener in self.listeners:
            if hasattr(listener, "record_batch"):
                listener.record_batch(ds.num_examples())
            listener.iteration_done(self, self.iteration)

    def _validate_labels(self, ds: DataSet) -> None:
        """Informative input validation (reference analogue:
        `exceptions/TestInvalidInput` error paths)."""
        from deeplearning4j_tpu.datasets.normalizers import OneHotEncoder

        ranges = getattr(ds, "_value_ranges", {})
        if isinstance(self._normalizer, OneHotEncoder):
            # device one_hot silently zero-rows an OOB id: fail loudly here
            self._normalizer.check_ids(ds.features,
                                       value_range=ranges.get("features"))
        out_layer = self.layers[-1]
        n_out = getattr(out_layer, "n_out", None)
        if ds.labels is None:
            raise ValueError("fit() requires labels; got DataSet with labels=None "
                             "(use pretrain() for unsupervised training)")
        # dtype/shape probes only — never np.asarray a device-resident
        # batch (that would download it through the host link every step)
        labels = (ds.labels if hasattr(ds.labels, "dtype")
                  else np.asarray(ds.labels))
        if np.issubdtype(labels.dtype, np.integer):
            # sparse class-id labels: width check is a range check instead;
            # sentinel ids on mask==0 positions are allowed (the loss clamps
            # the gather, masked rows contribute nothing)
            from deeplearning4j_tpu.ops.losses import check_sparse_label_range

            check_sparse_label_range(labels, n_out, mask=ds.labels_mask,
                                     value_range=ranges.get("labels"))
            return
        if n_out and labels.shape[-1] != n_out:
            raise ValueError(
                f"labels have width {labels.shape[-1]} but output layer "
                f"has n_out={n_out} (features shape {ds.features.shape}, "
                f"labels shape {labels.shape})")

    def _fit_tbptt(self, ds: DataSet):
        """Truncated BPTT (reference `doTruncatedBPTT`,
        `MultiLayerNetwork.java:1140-1194`): slice the time axis into
        tbptt_fwd_length windows, carrying LSTM (h, c) across windows; each
        window is one jitted step (fixed window shape ⇒ one compilation)."""
        # build windows (and run their label validation) BEFORE seeding the
        # transient carries, so a validation error can't leave batch-sized
        # transients in the persistent state slots; restore via try/finally
        # for mid-window failures (matches the CG container's ordering)
        windows = list(self._tbptt_windows(ds))
        saved = self._tbptt_seed_carries(ds.features.shape[0])
        losses = []
        try:
            for window in windows:
                self._fit_batch(window)
                losses.append(self._score)
        finally:
            # rnn carries are per-batch transients; restore persistent slots
            self._tbptt_restore_carries(saved)
        self.score_value = float(np.mean([np.asarray(l) for l in losses]))

    def _tbptt_applicable(self, ds) -> bool:
        """Does this batch train via tBPTT? 3-D sequences always; (B, T)
        integer ids when the first layer consumes id sequences
        (TokenEmbedding-style). Shared with ParallelWrapper's dispatch."""
        f = getattr(ds, "features", None)
        if f is None:
            return False
        nd = np.ndim(f)
        if nd == 3:
            return True
        l0 = self.layers[0]
        if not (nd == 2 and getattr(l0, "integer_input", False)
                and l0.input_kind == "rnn"):
            return False
        dt = f.dtype if hasattr(f, "dtype") else np.asarray(f).dtype
        return np.issubdtype(dt, np.integer)

    def _tbptt_seed_carries(self, B: int):
        """Seed zero (h, c) carries into every streaming-LSTM slot; returns
        the saved persistent states for `_tbptt_restore_carries`. Shared
        with ParallelWrapper's sharded tBPTT path."""
        saved = {}
        for i, layer in enumerate(self.layers):
            if isinstance(layer, GravesLSTM) and type(layer) is GravesLSTM:
                n = layer.n_out
                saved[i] = self._layer_state[i]
                self._layer_state[i] = {"h": jnp.zeros((B, n), self.dtype),
                                        "c": jnp.zeros((B, n), self.dtype)}
        return saved

    def _tbptt_restore_carries(self, saved) -> None:
        for i, st in saved.items():
            self._layer_state[i] = st

    def _tbptt_windows(self, ds: DataSet):
        """Fixed-shape tBPTT window batches: the time axis sliced
        into `tbptt_fwd_length` chunks, the tail chunk padded + masked so
        every window compiles to ONE shape. Validates per-timestep labels
        eagerly (both the single-chip fit path and ParallelWrapper's
        sharded path come through here)."""
        sparse = (ds.labels is not None
                  and np.issubdtype(np.asarray(ds.labels).dtype, np.integer)
                  and np.asarray(ds.labels).ndim == 2)
        if ds.labels is None or (ds.labels.ndim != 3 and not sparse):
            raise ValueError(
                "truncated BPTT requires per-timestep labels: one-hot "
                "(batch, time, nOut) or sparse int (batch, time); got "
                f"labels shape "
                f"{None if ds.labels is None else ds.labels.shape}. For "
                "sequence-to-one models, train without tBPTT "
                "(t_bptt_forward_length unset)")
        fwd_len = self.conf.tbptt_fwd_length
        T = ds.features.shape[1]
        B = ds.features.shape[0]
        n_windows = (T + fwd_len - 1) // fwd_len
        windows = []
        for w in range(n_windows):
            lo, hi = w * fwd_len, min((w + 1) * fwd_len, T)
            if hi - lo < fwd_len and n_windows > 1:
                # pad the tail window to fwd_len to avoid a recompilation;
                # padded steps are masked out
                pad = fwd_len - (hi - lo)
                feats = np.concatenate(
                    [ds.features[:, lo:hi], np.zeros_like(ds.features[:, :pad])], axis=1)
                labs = np.concatenate(
                    [ds.labels[:, lo:hi], np.zeros_like(ds.labels[:, :pad])], axis=1)
                m = np.concatenate(
                    [np.ones((B, hi - lo), np.float32), np.zeros((B, pad), np.float32)], axis=1)
                fmask = m if ds.features_mask is None else np.concatenate(
                    [ds.features_mask[:, lo:hi], np.zeros((B, pad), np.float32)], axis=1)
                lmask = m if ds.labels_mask is None else np.concatenate(
                    [ds.labels_mask[:, lo:hi], np.zeros((B, pad), np.float32)], axis=1)
                windows.append(DataSet(feats, labs, fmask, lmask))
            else:
                windows.append(DataSet(
                    ds.features[:, lo:hi], ds.labels[:, lo:hi],
                    None if ds.features_mask is None else ds.features_mask[:, lo:hi],
                    None if ds.labels_mask is None else ds.labels_mask[:, lo:hi]))
        return windows

    # ------------------------------------------------------------ inference
    def output(self, x: np.ndarray, train: bool = False) -> np.ndarray:
        """Forward pass returning output activations (reference
        `output:1540`). `train=True` uses batch statistics / dropout like the
        reference's train-mode activations (dropout rng derives from the
        current iteration)."""
        self._ensure_init()
        from deeplearning4j_tpu.nn.precision import wire_asarray

        x = wire_asarray(x, self.dtype, self._features_are_ids())
        if self._jit_output is None:
            def fwd(p, s, xx, rng, train):
                xx = self._prep_features(xx)
                return self._forward_pure(p, s, xx, train=train, rng=rng,
                                          fmask=None)[0]

            self._jit_output = jax.jit(fwd, static_argnames=("train",))
        rng = (jax.random.fold_in(jax.random.PRNGKey(self.conf.seed), self.iteration)
               if train else None)
        return np.asarray(self._jit_output(self._params, self._layer_state, x,
                                           rng, train))

    def feed_forward(self, x: np.ndarray) -> List[np.ndarray]:
        """All layer activations (reference `feedForward`)."""
        self._ensure_init()
        acts = []
        xx = self._prep_features(jnp.asarray(x))
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                xx = self.conf.preprocessors[i].preprocess(xx)
            xx, _ = layer.forward(self._params[i], self._layer_state[i], xx,
                                  train=False, rng=None)
            acts.append(np.asarray(xx))
        return acts

    def _check_sparse_labels(self, ds: DataSet) -> None:
        """Range-check sparse labels on the non-fit entry points too — the
        loss clamps the gather, so without this an out-of-range id would
        yield a plausible-but-wrong finite score instead of an error."""
        if ds.labels is None:
            return
        from deeplearning4j_tpu.ops.losses import check_sparse_label_range

        check_sparse_label_range(ds.labels,
                                 getattr(self.layers[-1], "n_out", None),
                                 mask=ds.labels_mask)

    def score(self, ds: DataSet, train: bool = False) -> float:
        """Loss on a dataset without updating (reference `score(DataSet)`)."""
        self._ensure_init()
        self._check_sparse_labels(ds)
        f, l, fm, lm = self._batch_arrays(ds)
        loss, _ = self._loss_pure(self._params, self._layer_state, f, l, fm, lm,
                                  None, train)
        return float(loss)

    def score_examples(self, ds: DataSet,
                       add_regularization: bool = False) -> np.ndarray:
        """Per-example loss scores, shape (B,) (reference
        `MultiLayerNetwork.scoreExamples:3169`: feed forward, then the
        output layer's computeScoreForExamples; time-distributed outputs
        sum masked per-timestep scores per sequence). With
        `add_regularization` the net's L1/L2 penalty is added to every
        example's score (reference adds `calcRegularizationScore` the same
        way). For unmasked single-step data, `mean(score_examples(ds))`
        equals `score(ds)` minus the regularization term."""
        self._ensure_init()
        self._check_sparse_labels(ds)
        f, l, fm, lm = self._batch_arrays(ds)
        f = self._prep_features(f)
        x, _ = self._forward_pure(self._params, self._layer_state, f,
                                  train=False, rng=None, fmask=fm,
                                  upto=len(self.layers) - 1)
        out_i = len(self.layers) - 1
        if out_i in self.conf.preprocessors:
            x = self.conf.preprocessors[out_i].preprocess(x)
        mask = lm if lm is not None else (fm if x.ndim == 3 else None)
        scores = self.layers[-1].score_array(
            self._params_of(self._params, len(self.layers) - 1), x, l,
                                             mask=mask)
        if add_regularization:
            scores = scores + self._reg_score(self._params)
        return np.asarray(scores)

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.argmax(self.output(x), axis=-1)

    def evaluate(self, iterator: Union[DataSetIterator, DataSet],
                 labels: Optional[List[str]] = None, top_n: int = 1):
        """Classification evaluation (reference `evaluate:2365`;
        `evaluate(iterator, labelsList, topN)` overload)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(labels=labels, top_n=top_n)
        if isinstance(iterator, DataSet):
            iterator = ListDataSetIterator([iterator])
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    # --------------------------------------------------------- rnn support
    def rnn_time_step(self, x: np.ndarray) -> np.ndarray:
        """Stateful single/multi-step inference (reference
        `rnnTimeStep:2196`): carries (h, c) between calls for streaming
        generation. The whole per-timestep layer walk is jitted ONCE; the
        Python loop only dispatches compiled steps — one dispatch per
        timestep instead of one per eager op."""
        from deeplearning4j_tpu.nn.conf.layers import (
            GravesBidirectionalLSTM,
            TokenEmbedding,
            TransformerBlock,
        )

        self._ensure_init()
        for i, layer in enumerate(self.layers):
            if isinstance(layer, GravesBidirectionalLSTM):
                raise ValueError(
                    f"rnn_time_step cannot stream through bidirectional "
                    f"LSTM layer {i} (the backward pass needs the full "
                    "sequence)")
            if isinstance(layer, TransformerBlock):
                raise ValueError(
                    f"rnn_time_step cannot stream through attention layer "
                    f"{i} — use models.transformer.generate (jitted KV-"
                    "cache sampler)")
        xx = jnp.asarray(x)
        token_seq = self._feature_wire_mode() == "sink" \
            and self.layers[0].input_kind == "rnn"
        temporal = xx.ndim == 3 or (token_seq and xx.ndim == 2)
        squeeze = not temporal
        T = xx.shape[1] if temporal else 1
        B = xx.shape[0]
        for i, layer in enumerate(self.layers):
            if isinstance(layer, GravesLSTM) and type(layer) is GravesLSTM \
                    and i not in self._rnn_state:
                n = layer.n_out
                self._rnn_state[i] = (jnp.zeros((B, n), self.dtype),
                                      jnp.zeros((B, n), self.dtype))
        if self._jit_rnn_step is None:
            def step_fn(params, lstate, rnn_state, x_t, pos):
                h = self._prep_features(x_t)
                new_rnn = dict(rnn_state)
                for i, layer in enumerate(self.layers):
                    if i in self.conf.preprocessors:
                        h = self.conf.preprocessors[i].preprocess(h)
                    if isinstance(layer, GravesLSTM) \
                            and type(layer) is GravesLSTM:
                        h, (hn, cn) = layer.step(params[i], h,
                                                 *rnn_state[i])
                        new_rnn[i] = (hn, cn)
                        continue
                    if isinstance(layer, TokenEmbedding):
                        idx = (h if h.ndim == 1 else h[:, 0]).astype(
                            jnp.int32)
                        h = params[i]["W"][idx]
                        if layer.positional:  # rope models carry no table
                            p = jnp.minimum(pos, layer.max_length - 1)
                            h = h + params[i]["P"][p]
                        continue
                    if h.ndim == 1:
                        h = h[:, None]   # single-step ids -> one timestep
                    elif h.ndim == 2 and layer.input_kind == "rnn" \
                            and not getattr(layer, "integer_input", False):
                        h = h[:, None, :]
                    h, _ = layer.forward(params[i], lstate[i], h,
                                         train=False, rng=None)
                    if h.ndim == 3 and h.shape[1] == 1:
                        h = h[:, 0]
                return h, new_rnn

            self._jit_rnn_step = jax.jit(step_fn)
        pos0 = getattr(self, "_rnn_pos", 0)
        outs = []
        for t in range(T):
            x_t = xx[:, t] if temporal else xx
            out, self._rnn_state = self._jit_rnn_step(
                self._params, self._layer_state, self._rnn_state, x_t,
                jnp.asarray(pos0 + t, jnp.int32))
            outs.append(out)
        self._rnn_pos = pos0 + T
        out = jnp.stack(outs, axis=1)
        if squeeze:
            out = out[:, 0]
        return np.asarray(out)

    def rnn_clear_previous_state(self):
        self._rnn_state = {}
        self._rnn_pos = 0

    def rnn_get_previous_state(self) -> Dict[int, Dict[str, np.ndarray]]:
        """Per-LSTM-layer streaming state plus the stream position (under
        the reserved key '__pos__' — TokenEmbedding's positional row is
        part of the streaming state). Reference `rnnGetPreviousState:2252`."""
        out: Dict = {i: {"h": np.asarray(h), "c": np.asarray(c)}
                     for i, (h, c) in self._rnn_state.items()}
        out["__pos__"] = getattr(self, "_rnn_pos", 0)
        return out

    def rnn_set_previous_state(self, states: Dict[int, Dict[str, np.ndarray]]) -> None:
        """(reference `rnnSetPreviousState:2262`)."""
        states = dict(states)
        self._rnn_pos = int(states.pop("__pos__", 0))
        self._rnn_state = {
            int(i): (jnp.asarray(st["h"], self.dtype),
                     jnp.asarray(st["c"], self.dtype))
            for i, st in states.items()}

    # ---------------------------------------------------- params / serde
    def params(self) -> np.ndarray:
        """Flat parameter vector (reference `Model.params()` — the flat view
        from `init():386`)."""
        self._ensure_init()
        flat, _ = ravel_pytree(self._params)
        return np.asarray(flat)

    def set_params(self, flat: np.ndarray) -> None:
        self._ensure_init()
        self._params = self._unravel(jnp.asarray(flat, self.dtype))

    def num_params(self) -> int:
        return int(self.params().shape[0])

    def summary(self) -> str:
        """Human-readable architecture table: per-layer type, in/out types,
        and parameter count (a UX convenience the 0.7.x reference lacks;
        later reference versions added the same shape under this name)."""
        self._ensure_init()
        rows = [("idx", "layer", "in", "out", "params")]
        total = 0
        for i, layer in enumerate(self.layers):
            it_in = self._input_types[i]
            it_out = layer.output_type(it_in)
            n = sum(int(np.prod(v.shape)) for v in self._params[i].values())
            total += n
            pre = "* " if i in self.conf.preprocessors else ""
            rows.append((str(i), pre + type(layer).__name__, str(it_in),
                         str(it_out), f"{n:,}"))
        from deeplearning4j_tpu.util.text_table import format_table

        return format_table(
            rows, f"total parameters: {total:,}"
            + ("  (* = input preprocessor applied)"
               if self.conf.preprocessors else ""))

    def compute_gradient_and_score(self, ds: DataSet) -> Tuple[np.ndarray, float]:
        """Analytic flat gradient + score at current params (reference
        `Model.computeGradientAndScore` / `gradient()` used by
        `GradientCheckUtil.java:62`). Deterministic: no dropout rng."""
        self._ensure_init()
        self._check_sparse_labels(ds)
        f, l, fm, lm = self._batch_arrays(ds)

        def lf(p):
            loss, _ = self._loss_pure(p, self._layer_state, f, l, fm, lm, None, True)
            return loss

        loss, grads = jax.value_and_grad(lf)(self._params)
        flat, _ = ravel_pytree(grads)
        return np.asarray(flat), float(loss)

    def score_function(self, ds: DataSet):
        """Jitted flat-params → loss closure over a fixed batch, for the
        gradient-check harness (numeric central differences)."""
        self._ensure_init()
        self._check_sparse_labels(ds)
        f, l, fm, lm = self._batch_arrays(ds)
        _, unravel = ravel_pytree(self._params)

        @jax.jit
        def score_at(flat):
            loss, _ = self._loss_pure(unravel(flat), self._layer_state, f, l,
                                      fm, lm, None, True)
            return loss

        return score_at

    # ------------------------------------------------------------ pretrain
    def pretrain(self, iterator: DataSetIterator, epochs: int = 1) -> None:
        """Greedy layerwise unsupervised pretraining for any layer exposing
        `pretrain_loss` — AutoEncoder, RBM (CD-k surrogate), VAE (neg-ELBO)
        (reference `MultiLayerNetwork.pretrain`, `:993`)."""
        self._ensure_init()
        for i, layer in enumerate(self.layers):
            if not hasattr(layer, "pretrain_loss"):
                continue
            cfg = layer.updater_cfg

            def step(p_i, u_i, feats, rng, iteration):
                def lf(p):
                    # same wire-dtype/normalizer prep as the supervised step
                    fx = self._prep_features(feats)
                    # encode input through the preceding (frozen) layers
                    x, _ = self._forward_pure(self._params, self._layer_state,
                                              fx, train=False, rng=None,
                                              fmask=None, upto=i)
                    return layer.pretrain_loss(p, x, rng)

                loss, g = jax.value_and_grad(lf)(p_i)
                p_new, u_new = apply_layer_update(layer, u_i, p_i, g, iteration)
                return p_new, u_new, loss

            # graftlint: disable=recompile  compiled once per pretraining
            # LAYER (the closure binds the layer), then reused across the
            # whole epoch loop below — not a per-iteration retrace
            jstep = jax.jit(step)
            it_count = 0
            for _ in range(epochs):
                for ds in iterator:
                    f, _, _, _ = self._batch_arrays(ds)
                    rng = jax.random.fold_in(jax.random.PRNGKey(self.conf.seed + i), it_count)
                    p_new, u_new, loss = jstep(self._params[i], self._upd_state[i],
                                               f, rng, jnp.asarray(it_count, jnp.int32))
                    self._params[i] = p_new
                    self._upd_state[i] = u_new
                    self.score_value = float(loss)
                    it_count += 1

    # ------------------------------------------------------------- helpers
    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def get_updater_state(self):
        return self._upd_state

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(self.conf, self.dtype,
                                compute_dtype=self.compute_dtype)
        net._normalizer = self._normalizer  # stateless transform: share
        if self._params is not None:
            net.init()
            net.set_params(self.params())
            # deep-copy: the jitted train step DONATES these buffers, so
            # aliasing them between clones would let either net's step delete
            # the other's arrays
            net._upd_state = jax.tree.map(jnp.copy, self._upd_state)
            net._layer_state = jax.tree.map(jnp.copy, self._layer_state)
        # clock must travel with the optimizer state, or resumed training
        # restarts Adam bias correction / LR schedules at t=0
        net.iteration = self.iteration
        net.epoch = self.epoch
        net.score_value = self.score_value
        return net
