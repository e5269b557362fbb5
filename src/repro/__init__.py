"""repro: HeterMoE (zebra parallelism + Asym-EA) reproduced as a JAX framework.

Public surface:
    repro.configs   — architecture configs (10 assigned archs + paper's Mixtral set)
    repro.models    — pure-JAX model zoo
    repro.core      — zebra parallelism, Asym-EA, planner, simulator
    repro.train     — training loop, optimizer, mixed precision
    repro.serve     — KV-cache serving
    repro.launch    — mesh / compile cache / train / serve entry points
"""

__version__ = "0.1.0"
