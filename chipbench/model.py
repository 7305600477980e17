"""The CNN family's program loss under its former name, for callers
outside the benchmark (``tests/test_pool_grad.py``).  The family lives in
``chipbench/families/cnn.py``."""
from chipbench.harness import load_family

loss_fn = load_family("cnn")[0].loss_fn
