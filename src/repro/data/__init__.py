"""Data substrate: feature hashing, synthetic paired-view corpora
(Europarl stand-in with planted correlations), and LM token pipelines."""

from .hashing import HashingFeaturizer
from .synthetic import (PlantedCCAData, SyntheticTokenStream, planted_views,
                        synth_paired_docs)

__all__ = [
    "HashingFeaturizer",
    "PlantedCCAData",
    "SyntheticTokenStream",
    "planted_views",
    "synth_paired_docs",
]
