from predictionio_tpu.models.sequence.engine import (
    SequenceAlgorithm,
    SequenceAlgorithmParams,
    SequenceDataSource,
    SequenceModel,
    SequencePreparator,
    sequence_engine,
)

__all__ = [
    "SequenceAlgorithm",
    "SequenceAlgorithmParams",
    "SequenceDataSource",
    "SequenceModel",
    "SequencePreparator",
    "sequence_engine",
]
