"""Moved: a family's plain reference is ``families/<family>/reference.py``
(``contract.load_family``). ``tests/test_mistral_w8kv8.py`` still
imports ``reference_logits`` from here, with the keyword signature it
had, and a benchmark PR may not edit ``tests/``: see
``contract.first_family``. When that import is gone, so is this file."""
from benchmark.harness import contract

reference_logits = contract.first_family("reference").logits_by_dims
