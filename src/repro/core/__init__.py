"""Photonic execution model + energy model."""

from repro.core import energy, feedback, photonics
from repro.core.feedback import FeedbackConfig
from repro.core.photonics import PhotonicBackend, PhotonicConfig, preset

__all__ = [
    "energy", "feedback", "photonics",
    "FeedbackConfig", "PhotonicBackend", "PhotonicConfig", "preset",
]
