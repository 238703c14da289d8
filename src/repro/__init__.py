"""repro — a federated function-as-a-service framework for TPU fleets,
reproducing funcX (Li et al., IEEE TPDS 2022) with a JAX/Pallas substrate.

Layers (see DESIGN.md):
  - ``repro.core``       the funcX contribution: federated FaaS runtime
  - ``repro.data``       intra/inter-endpoint data management
  - ``repro.models``     the 10 assigned architectures (pure JAX)
  - ``repro.kernels``    Pallas TPU kernels (no model path calls them)
  - ``repro.train``/``repro.serve``  substrate for the two step kinds
  - ``repro.launch``     train and serve drivers
"""

__version__ = "1.0.0"
