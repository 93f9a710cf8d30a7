"""Registry of conv models that lower through the streaming-graph IR.

The serving engine, launcher, and benchmarks look models up here by name
(``get_conv_model``), so none of them hard-codes any particular network —
adding a model is one ``register_conv_model`` call exposing the two
things the engine needs: an ``init_params`` and a ``to_graph`` exporter
(``core/graph.py:StreamGraph``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict

__all__ = ["ConvModelSpec", "register_conv_model", "get_conv_model",
           "conv_model_names", "compile_forward", "bucket_compiler"]


@dataclasses.dataclass(frozen=True)
class ConvModelSpec:
    """One registered conv model.

    ``init_params(key, *, width_mult, img, classes)`` builds the param
    tree; ``to_graph()`` exports the ``StreamGraph`` the engine lowers.
    """
    name: str
    init_params: Callable
    to_graph: Callable

    def graph(self):
        return self.to_graph()


_REGISTRY: Dict[str, ConvModelSpec] = {}


def register_conv_model(name: str, init_params: Callable,
                        to_graph: Callable) -> ConvModelSpec:
    spec = ConvModelSpec(name=name, init_params=init_params,
                         to_graph=to_graph)
    _REGISTRY[name] = spec
    return spec


def conv_model_names():
    """Registered model names, sorted (the launcher's --model choices)."""
    _ensure_builtin()
    return sorted(_REGISTRY)


def get_conv_model(name: str) -> ConvModelSpec:
    _ensure_builtin()
    spec = _REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"unknown conv model {name!r} "
                       f"(registered: {', '.join(sorted(_REGISTRY))})")
    return spec


def compile_forward(model, params, *, img: int, batch: int = 1,
                    chan: int = 3, **compile_kw):
    """Compile a registered model's graph into a static fold schedule +
    jitted forward — the one compile surface all models share (the
    per-model ``compile_forward`` wrappers delegate here).  ``model`` is
    a registry name or a ``ConvModelSpec``; ``compile_kw`` is forwarded
    to ``core/engine.py:compile_network`` (policy, cache, autotune, ...).
    """
    from repro.core.engine import compile_network
    spec = model if isinstance(model, ConvModelSpec) else \
        get_conv_model(model)
    return compile_network(params, spec.to_graph(),
                           (batch, chan, img, img), **compile_kw)


def bucket_compiler(model, params, *, img: int, chan: int = 3,
                    **compile_kw):
    """The serving compile surface for a registered model: one memoized
    compiled forward per batch-bucket width over one shared
    ``ScheduleCache`` (``core/engine.py:BucketCompiler``)."""
    from repro.core.engine import BucketCompiler
    spec = model if isinstance(model, ConvModelSpec) else \
        get_conv_model(model)
    return BucketCompiler(params, spec.to_graph(), img, chan=chan,
                          **compile_kw)


def _ensure_builtin() -> None:
    """Register the built-in models lazily (import cycles stay trivial:
    model modules never import the zoo)."""
    if "vgg16" not in _REGISTRY:
        from repro.models import vgg
        register_conv_model("vgg16", vgg.init_params, vgg.to_graph)
    if "resnet18" not in _REGISTRY:
        from repro.models import resnet
        register_conv_model("resnet18", resnet.init_params, resnet.to_graph)
    if "mobilenetv2" not in _REGISTRY:
        from repro.models import mobilenet
        for strides, classes in ((mobilenet.CIFAR, mobilenet.n_classes),
                                 (mobilenet.IMAGENET, 1000)):
            register_conv_model(
                strides.name,
                functools.partial(mobilenet.init_params, classes=classes),
                functools.partial(mobilenet.to_graph, strides))
