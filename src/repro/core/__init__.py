"""D2FT planning layer — everything host-side and static.

Scores (``scores``) feed the bi-level knapsack (``knapsack``) which emits
an Algorithm-1 ``Schedule`` (``schedule``); ``assignment`` solves Eq. 4's
multiple-knapsack to place micro-batches on devices; ``cost_model`` prices
tables, ``baselines`` implements the paper's comparison schedulers,
``lora`` the D2FT-LoRA variant, and ``d2ft`` is the planning facade.
Plans are numpy + Python ints; the execution layers (models/, kernels/,
train/) consume them as gates and static jit constants.
See docs/architecture.md for the planning-vs-execution split.
"""
