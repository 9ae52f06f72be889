"""The benchmark's harness: manifest, traffic, client, scoring, server
child, trace reduction and the correctness check. Nothing here imports the
program (``vllm_tpu``) except ``serve_child.py``, which is the server."""
