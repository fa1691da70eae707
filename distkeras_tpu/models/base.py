"""Model abstraction.

The reference treats "a model" as a compiled Keras object that is serialized with
``utils.serialize_keras_model`` and re-compiled per worker
(``workers.py -> Worker.prepare_model``). Here a :class:`Model` is an immutable pair
(flax module, parameter pytree): pure-functional so a *replica* is just another copy of
the params — stacking replicas along a mesh axis is a ``jax.tree`` operation, not a
re-deserialization.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.runtime.serialization import (
    register_model_class,
    serialize_model,
)


#: A mutable collection of float32 counts that a module adds to at every step
#: (``self.variable(ROUND_COUNTERS, ...)``): what a round routed where. It
#: rides the state path like BatchNorm's statistics, with two differences:
#: ``workers.make_local_loop`` zeroes it as a round begins, so that it leaves
#: the round program holding that round's sums, and the run loop
#: (``parallel/engine.py::run_per_round``) copies it out beside the loss and
#: hands it, a round later and as numpy, to the module's
#: ``publish_round_counters(round, counters)``, which owns the telemetry names.
ROUND_COUNTERS = "round_counters"

#: The collection that holds each routed layer's expert bias
#: (``blocks.RoutedFFN``): state a training step updates, like BatchNorm's
#: statistics, and never a gradient's.
ROUTER_BIAS = "router_bias"


def _coerce(v):
    # JSON round-trips tuples as lists; flax module fields want tuples back.
    return tuple(_coerce(x) for x in v) if isinstance(v, list) else v


_uint8_warned = [False]


def _warn_uint8_rescale() -> None:
    """One-time (per process) notice that the silent uint8 ``/255`` rule
    fired — so a byte-valued NON-image feature store (mask, categorical
    bytes) is never rescaled without a trace. Called from every site that
    applies the rule (here and ``workers.make_local_loop``); fires at trace
    time on jitted paths, which is exactly once per executable."""
    if _uint8_warned[0]:
        return
    _uint8_warned[0] = True
    import warnings

    warnings.warn(
        "uint8 features detected: applying the raw-image-bytes rule "
        "(x / 255 as float32) on every train/predict path. If these bytes "
        "are NOT an image, opt out with normalize_uint8=False on the "
        "Model / Trainer / ModelPredictor.", stacklevel=3)


def normalize_features(x, normalize_uint8: bool = True):
    """uint8 feature arrays are raw image bytes: ``x/255`` as float32.

    The one normalization rule, shared by the training loop
    (``workers.make_local_loop``, which additionally casts to the compute
    dtype) and every inference path (:meth:`Model.apply`,
    ``predictors.ModelPredictor``) — uint8 stores must see identical inputs
    train-side and predict-side. Integer token/label inputs are int32/int64
    and pass through untouched.

    ``normalize_uint8=False`` opts out for byte-valued non-image features
    (masks, byte categoricals): the array passes through untouched. The
    flag threads from ``Model.normalize_uint8`` through Trainer and
    ModelPredictor so train and predict can never disagree; when the rule
    DOES fire on a uint8 store, a one-time warning says so."""
    if normalize_uint8 and getattr(x, "dtype", None) == jnp.uint8:
        _warn_uint8_rescale()
        return x.astype(jnp.float32) / 255.0
    return x


class DKModule(nn.Module):
    """Base class for zoo modules: adds the config round-trip used by serialization."""

    def get_config(self) -> dict[str, Any]:
        return {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if f.name not in ("parent", "name")
        }

    @classmethod
    def from_config(cls, kwargs: dict[str, Any]) -> "DKModule":
        return cls(**{k: _coerce(v) for k, v in kwargs.items()})


def register_model(cls: type) -> type:
    """Class decorator: make ``cls`` reconstructible from a serialized spec."""
    register_model_class(cls.__name__, cls)
    return cls


@dataclasses.dataclass
class Model:
    """(module, params) bundle with the serialization surface of a Keras model.

    ``sample_spec`` (shapes/dtypes of the build-time sample input) is retained so
    replicas can be *re-initialized* with fresh PRNG keys — the reference got
    per-executor re-init for free from ``uniform_weights`` + model deserialization
    per worker; here :meth:`reinit_params` provides it functionally.
    """

    module: nn.Module
    params: Any
    sample_spec: Any = None
    #: mutable non-param variable collections, e.g. {"batch_stats": tree} for
    #: flax BatchNorm models or {"keras_state": [...]} for carried Keras
    #: non-trainables. None for pure-functional models. Engines thread these
    #: through training and cross-replica-mean them at each fold.
    state: Any = None
    #: apply the raw-image-bytes rule (uint8 -> /255 float32) on every
    #: train/predict input. ``False`` opts byte-valued non-image features
    #: out; the engines and predictors read THIS flag, so train and
    #: inference can never disagree.
    normalize_uint8: bool = True

    @classmethod
    def build(
        cls,
        module: nn.Module,
        sample_input: Any,
        seed: int = 0,
        normalize_uint8: bool = True,
    ) -> "Model":
        """Initialize parameters by tracing ``module`` on ``sample_input``.

        ``sample_input`` may be a single array or a tuple of arrays. Shapes only are
        used (abstract init under ``jax.eval_shape`` would also work, but a concrete
        init keeps custom modules simple).
        """
        from distkeras_tpu import telemetry

        inputs = sample_input if isinstance(sample_input, tuple) else (sample_input,)
        with telemetry.span("model_build"):
            variables = module.init(jax.random.key(seed), *inputs, train=False)
        params = variables["params"]
        state = {k: v for k, v in variables.items() if k != "params"} or None
        spec = tuple(jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype)
                     for a in inputs)
        return cls(module=module, params=params, sample_spec=spec,
                   state=state, normalize_uint8=normalize_uint8)

    def apply(self, params, *inputs, train: bool = False, rng=None, state=None):
        """Pure forward pass — the jit-safe core of ``model.predict``/``train_on_batch``.

        Inference-mode by default: mutable collections (``state`` or the
        model's own) are read, never updated. uint8 feature arrays are
        normalized ``x/255`` exactly as the training loop does
        (``workers.make_local_loop``) — train/inference inputs must never
        skew for raw-byte image stores.
        """
        rngs = {"dropout": rng} if rng is not None else None
        variables = {"params": params, **((state if state is not None
                                           else self.state) or {})}
        inputs = tuple(normalize_features(x, self.normalize_uint8)
                       for x in inputs)
        return self.module.apply(variables, *inputs, train=train, rngs=rngs)

    def predict(self, *inputs):
        return self.apply(self.params, *inputs, train=False)

    def with_params(self, params) -> "Model":
        return dataclasses.replace(self, params=params)

    def with_state(self, state) -> "Model":
        return dataclasses.replace(self, state=state)

    def with_module(self, module) -> "Model":
        """Same params under a differently-configured module (e.g. rebinding
        a TransformerLM with ``seq_axis`` set for sequence parallelism —
        hyperparameter-only clones share the parameter structure)."""
        return dataclasses.replace(self, module=module)

    @property
    def state_collections(self) -> tuple:
        """Names of the mutable collections (() for pure models)."""
        return tuple(self.state) if self.state else ()

    def reinit_params(self, seed: int):
        """Fresh parameters drawn with a different PRNG key (ensemble diversity).

        Models built via :meth:`build` re-trace the module's own initializers on
        the recorded sample spec. Models without one (deserialized or
        Keras-ingested) fall back to permuting each float leaf's elements — a
        random permutation of an i.i.d. init draw is another draw from the same
        empirical distribution, and constant-init leaves (biases) are fixed
        points of it, matching a true re-init.
        """
        if self.sample_spec is not None:
            inputs = tuple(jnp.zeros(s.shape, s.dtype) for s in self.sample_spec)
            variables = self.module.init(jax.random.key(seed), *inputs, train=False)
            return variables["params"]
        leaves, treedef = jax.tree.flatten(self.params)
        keys = jax.random.split(jax.random.key(seed), len(leaves))
        new = [
            jax.random.permutation(k, jnp.ravel(x)).reshape(jnp.shape(x))
            if jnp.issubdtype(x.dtype, jnp.floating) and x.size > 1 else x
            for k, x in zip(keys, leaves)
        ]
        return jax.tree.unflatten(treedef, new)

    def spec(self) -> dict[str, Any]:
        return {"class": type(self.module).__name__, "kwargs": self.module.get_config()}

    def serialize(self) -> bytes:
        return serialize_model(self)

    @property
    def num_params(self) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(self.params))

    def summary(self) -> str:
        lines = [f"Model: {type(self.module).__name__}  ({self.num_params:,} params)"]
        flat = jax.tree_util.tree_flatten_with_path(self.params)[0]
        for path, leaf in flat:
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            lines.append(f"  {name}: {tuple(leaf.shape)} {leaf.dtype}")
        return "\n".join(lines)


def uniform_weights(model: Model, bounds: tuple[float, float] = (-0.5, 0.5),
                    seed: int = 0) -> Model:
    """Re-init every weight uniformly in ``bounds``.

    Parity: ``distkeras/utils.py -> uniform_weights(model, constraints)``.
    """
    lo, hi = bounds
    leaves, treedef = jax.tree.flatten(model.params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    new = [
        (jax.random.uniform(k, x.shape, x.dtype, lo, hi)
         if jnp.issubdtype(x.dtype, jnp.floating) else x)
        for k, x in zip(keys, leaves)
    ]
    return model.with_params(jax.tree.unflatten(treedef, new))
