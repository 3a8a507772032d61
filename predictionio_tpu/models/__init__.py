"""Official engine templates, re-designed TPU-first.

Parity targets (reference examples/):
  - recommendation: explicit ALS (scala-parallel-recommendation)
  - similarproduct: implicit ALS + cosine similarity (scala-parallel-similarproduct)
  - classification: Naive Bayes / logistic regression (scala-parallel-classification)
  - ecommerce: ALS + business-rule filters (scala-parallel-ecommercerecommendation)
  - ncf: deep two-tower/NCF with sharded embeddings (pypio deep-rec config)
  - external: serve externally-trained models through DASE (e2 PythonEngine)
  - sequence: next-item prediction over each entity's ordered history with
    the Olmo-Hybrid block — gated delta-rule linear attention 3:1 with full
    attention (huggingface.co/allenai/Olmo-Hybrid-7B config.json;
    arXiv:2412.06464); its kernels load when it trains, not at import

Importing this package registers every bundled engine factory (the reflective
EngineFactory discovery analog, workflow/WorkflowUtils.scala:47).
"""

from predictionio_tpu.models import (  # noqa: F401
    classification,
    ecommerce,
    external,
    ncf,
    recommendation,
    sequence,
    similarproduct,
)
