"""repro — reproduction of "State access patterns in embarrassingly parallel
computations" grown into a JAX/Pallas streaming system.

Subsystem map (see README.md for the full tour):

* :mod:`repro.core` — the paper's §4 state access patterns (S1..S5), serial
  semantics oracles, analytic models, discrete-event simulator.
* :mod:`repro.runtime` — elastic streaming runtime: sources/backpressure,
  pattern-agnostic executor, autoscaler driving the §4.x adaptivity
  protocols, telemetry, failure supervisor.
* :mod:`repro.models` / :mod:`repro.kernels` — transformer/SSM/MoE substrate
  and Pallas kernels.
* :mod:`repro.serving` / :mod:`repro.ft` / :mod:`repro.launch` — the
  applications: continuous-batching serving (S2 session store),
  fault-tolerant training (S3/S4/S5), multi-pod launch tooling.
"""
