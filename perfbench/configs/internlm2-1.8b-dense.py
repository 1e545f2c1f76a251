"""The unpruned checkpoint and plain float32 reference of internlm2-1.8b-dense.

The same InternLM2-1.8B decoder, seeded weights and reference as
internlm2-1.8b-tcsc30, whose module this one loads: this configuration
states no density, so no matrix is pruned, and the program serves every
projection and the head through its dense matmuls.
"""
from __future__ import annotations

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "internlm2_reference",
    pathlib.Path(__file__).with_name("internlm2-1.8b-tcsc30.py"))
_internlm2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_internlm2)

checkpoint_matrix = _internlm2.checkpoint_matrix
gaps = _internlm2.gaps
first_choices = _internlm2.first_choices
