"""Bucket-budget knob: bound the worst-case number of step compilations.

Reference analog: the CUDA-graph capture list (``cudagraph_dispatcher``)
is the reference's compile-count control; here the knob thins the derived
pow2 bucket ladders until token_buckets x request_buckets fits."""

import pytest

from vllm_tpu.config import CompilationConfig, SchedulerConfig


def _sched():
    return SchedulerConfig(max_num_batched_tokens=8192, max_num_seqs=512)


def test_default_buckets_unthinned():
    cc = CompilationConfig()
    cc.finalize(_sched())
    assert cc.token_buckets == [16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192]
    assert cc.request_buckets == [8, 16, 32, 64, 128, 256, 512]


def test_budget_thins_but_keeps_endpoints():
    cc = CompilationConfig(max_step_compilations=16)
    cc.finalize(_sched())
    assert len(cc.token_buckets) * len(cc.request_buckets) <= 16
    # Endpoints survive: smallest bucket bounds minimum padding, largest
    # must still admit a full batch.
    assert cc.token_buckets[0] == 16 and cc.token_buckets[-1] == 8192
    assert cc.request_buckets[0] == 8 and cc.request_buckets[-1] == 512
    assert cc.token_buckets == sorted(cc.token_buckets)


def test_explicit_buckets_never_thinned():
    cc = CompilationConfig(token_buckets=[64, 8192], max_step_compilations=4)
    cc.finalize(_sched())
    assert cc.token_buckets == [64, 8192]


def test_tiny_budget_terminates():
    cc = CompilationConfig(max_step_compilations=1)
    cc.finalize(_sched())
    # Cannot reach 1 (endpoints are kept) but must terminate at 2x2.
    assert len(cc.token_buckets) == 2 and len(cc.request_buckets) == 2


@pytest.mark.parametrize("tokens,requests,seqs,pages", [
    # Derived ladders, and explicit ones that leave more than one shape
    # (the pinned diffusion cell): the page ladder as it always was.
    ([], [], 512, [16, 32, 64, 128, 256, 512, 768]),
    ([8192], [128, 512], 512, [16, 32, 64, 128, 256, 512, 768]),
    ([8192], [], 512, [16, 32, 64, 128, 256, 512, 768]),
    # A derived ladder of one entry is not a pinned one.
    ([], [], 8, [16, 32, 64, 128, 256, 512, 768]),
    # One explicit shape of tokens and rows: the whole table alone.
    ([8192], [512], 512, [768]),
], ids=["derived", "two_row_buckets", "tokens_only", "derived_single", "one_shape"])
def test_one_pinned_step_shape_keeps_one_page_bucket(tokens, requests, seqs, pages):
    cc = CompilationConfig(token_buckets=tokens, request_buckets=requests)
    cc.finalize(SchedulerConfig(max_num_batched_tokens=8192, max_num_seqs=seqs))
    assert cc.page_buckets(768) == pages
    assert cc.page_buckets(8) == [8]
