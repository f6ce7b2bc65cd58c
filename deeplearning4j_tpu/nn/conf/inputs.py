"""Input types for shape inference.

TPU-native equivalent of reference ``nn/conf/inputs/InputType.java``: a small
algebra describing activations flowing between layers, used by the ListBuilder's
``setInputType`` pass to infer ``nIn`` and auto-insert preprocessors
(reference ``NeuralNetConfiguration.java:215-324``).

Convolutional activations are described by (height, width, channels) as in the
reference; the runtime lays them out NHWC internally (TPU-friendly) while the
user-facing tensors keep the reference's NCHW convention.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .serde import register

__all__ = ["InputType", "InputTypeFeedForward", "InputTypeRecurrent",
           "InputTypeLoopedRecurrent", "InputTypeConvolutional",
           "InputTypeConvolutionalFlat"]


@register
@dataclasses.dataclass
class InputTypeFeedForward:
    size: int = 0

    def arity(self):
        return self.size


@register
@dataclasses.dataclass
class InputTypeRecurrent:
    size: int = 0
    timeseries_length: Optional[int] = None

    def arity(self):
        return self.size


@register
@dataclasses.dataclass
class InputTypeLoopedRecurrent(InputTypeRecurrent):
    """A sequence activation after each of ``passes`` passes of a looped
    stack, stacked on a leading axis: [passes, b, T, size]."""
    passes: int = 1


@register
@dataclasses.dataclass
class InputTypeConvolutional:
    height: int = 0
    width: int = 0
    channels: int = 0

    def arity(self):
        return self.height * self.width * self.channels


@register
@dataclasses.dataclass
class InputTypeConvolutionalFlat:
    height: int = 0
    width: int = 0
    channels: int = 0

    def arity(self):
        return self.height * self.width * self.channels


class InputType:
    """Factory namespace matching the reference's static methods."""

    FeedForward = InputTypeFeedForward
    Recurrent = InputTypeRecurrent
    Convolutional = InputTypeConvolutional
    ConvolutionalFlat = InputTypeConvolutionalFlat

    @staticmethod
    def feed_forward(size):
        return InputTypeFeedForward(int(size))

    # reference-style camelCase aliases
    feedForward = feed_forward

    @staticmethod
    def recurrent(size, timeseries_length=None):
        return InputTypeRecurrent(int(size), timeseries_length)

    @staticmethod
    def convolutional(height, width, channels):
        return InputTypeConvolutional(int(height), int(width), int(channels))

    @staticmethod
    def convolutional_flat(height, width, channels):
        return InputTypeConvolutionalFlat(int(height), int(width), int(channels))

    convolutionalFlat = convolutional_flat
