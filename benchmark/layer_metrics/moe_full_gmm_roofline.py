"""Layer: kernels (layers/moe.py with EVERY expert of the router held,
megablox ``gmm``). Share of its roofline that the grouped matmul reaches in
a model that holds all its experts on the chip and does not generate by
diffusion.

``moe_gmm_roofline`` under a name of its own: the accepted metric's list of
cells is held to ``["sdar30b.chat"]`` by an accepted test that a PR may not
edit (PERF.md, Open questions), and its counting holds here as it stands:
the widths are the configuration's (``hidden_size``,
``moe_intermediate_size``), ``vllm:moe_experts_hit_total`` counts the
experts that got a live token a layer a step and
``vllm:moe_assignments_total`` the live tokens' pairs, so the bytes and the
operations are those of the experts this chip reads
(``benchmark/kernels/moe_gmm.py``). The reader IS that metric's, loaded from
its file beside this one."""


import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_layer_metrics_moe_gmm_roofline", Path(__file__).with_name("moe_gmm_roofline.py"))
_accepted = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_accepted)
read = _accepted.read
