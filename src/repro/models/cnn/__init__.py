"""CNN zoo with the BFP conv datapath (paper-faithful models).

``MODELS`` registers the paper's four test models (VGG16, ResNet-18/50,
GoogLeNet) plus the small in-repo trainable ones behind a uniform
:class:`CnnSpec` (init / apply / input geometry), so the serving stack
(``serve.cnn`` / ``launch.serve_cnn``) and the benchmarks enumerate them
by name instead of hand-wiring each module.  ``reduced=True`` builds the
tier-1-sized configuration of the same family (identical code paths,
shrunk widths), exactly the shapes the test suite exercises.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

from repro.models.cnn import googlenet as _googlenet
from repro.models.cnn import resnet as _resnet
from repro.models.cnn import small as _small
from repro.models.cnn import vgg as _vgg

__all__ = ["CnnSpec", "MODELS", "head_logits"]


def head_logits(out):
    """Classifier logits from an ``apply()`` output.

    An apply with auxiliary heads (``googlenet.apply`` with
    ``with_aux=True``) returns a tuple whose head 0 is the classifier;
    the registered applies return the logits array directly.  Training
    and evaluation code (``repro.train.cnn``, the serve engines) go
    through this one helper so every registered model trains with the
    same loss plumbing.
    """
    return out[0] if isinstance(out, tuple) else out


@dataclasses.dataclass(frozen=True)
class CnnSpec:
    """One registered CNN: how to build it and what it eats.

    ``apply(params, x, policy)`` is the served forward and returns the
    classifier logits (GoogLeNet's computes no aux head).
    """

    name: str
    init: Callable[..., Any]        #: init(key, *, reduced: bool) -> params
    apply: Callable[..., Any]       #: apply(params, x, policy) -> logits
    full_hw: int                    #: full-scale input H == W
    reduced_hw: int                 #: tier-1 / smoke input H == W
    in_ch: int = 3

    def input_shape(self, *, reduced: bool = True) -> Tuple[int, int, int]:
        hw = self.reduced_hw if reduced else self.full_hw
        return (hw, hw, self.in_ch)


def _vgg16_init(key, *, reduced: bool = True, num_classes: int = 10):
    if reduced:
        return _vgg.init(key, num_classes, width_mult=0.125, input_hw=32,
                         fc_dim=64)
    return _vgg.init(key, 1000)


def _resnet18_init(key, *, reduced: bool = True, num_classes: int = 10):
    if reduced:
        return _resnet.init(key, 18, num_classes, width_mult=0.25,
                            stage_depths=(1, 1, 1, 1))
    return _resnet.init(key, 18, 1000)


def _resnet50_init(key, *, reduced: bool = True, num_classes: int = 10):
    if reduced:
        return _resnet.init(key, 50, num_classes, width_mult=0.125,
                            stage_depths=(1, 1, 1, 1))
    return _resnet.init(key, 50, 1000)


def _googlenet_init(key, *, reduced: bool = True, num_classes: int = 10):
    """The served tree: no aux heads (``googlenet.init(with_aux=True)``
    builds them for training and the analysis)."""
    if reduced:
        return _googlenet.init(key, num_classes, width_mult=0.125,
                               with_aux=False)
    return _googlenet.init(key, 1000, with_aux=False)


def _lenet_init(key, *, reduced: bool = True, num_classes: int = 10):
    return _small.lenet_init(key, num_classes)


def _cifarnet_init(key, *, reduced: bool = True, num_classes: int = 10):
    return _small.cifarnet_init(key, num_classes)


MODELS: Dict[str, CnnSpec] = {
    "vgg16": CnnSpec("vgg16", _vgg16_init, _vgg.apply, 224, 32),
    "resnet18": CnnSpec("resnet18", _resnet18_init, _resnet.apply,
                        224, 32),
    "resnet50": CnnSpec("resnet50", _resnet50_init, _resnet.apply,
                        224, 32),
    "googlenet": CnnSpec("googlenet", _googlenet_init,
                         _googlenet.serve_apply, 224, 64),
    "lenet": CnnSpec("lenet", _lenet_init, _small.lenet_apply,
                     28, 28, in_ch=1),
    "cifarnet": CnnSpec("cifarnet", _cifarnet_init, _small.cifarnet_apply,
                        32, 32),
}
