"""Mesh-level behaviour (8 host devices, subprocess — see conftest)."""


def _assert_ok(results, name):
    r = results[name]
    assert r.get("ok"), f"{name}: {r}"


def test_collective_patterns(multidevice_results):
    """1-1/scatter/gather/gather_all/broadcast/all_to_all semantics on a mesh."""
    _assert_ok(multidevice_results, "patterns")


def test_sharded_train_matches_single_device(multidevice_results):
    """(2 data, 2 model) loss equals the unsharded loss on the same batch."""
    _assert_ok(multidevice_results, "sharded_train")


def test_seq_parallel_attention_plan(multidevice_results):
    """Heads that don't divide the model axis switch to the seq plan and
    still reproduce the unsharded numerics."""
    _assert_ok(multidevice_results, "seq_parallel_attention")


def test_sharded_decode_writes_its_rows_locally(multidevice_results):
    """With the KV cache's positions split over the model axis, a decode
    step changes only its rows, matches the unsharded step, and gathers no
    cache leaf."""
    _assert_ok(multidevice_results, "sharded_decode")


def test_moe_expert_parallel_matches_dense_oracle(multidevice_results):
    """EP-sharded MoE dispatch == dense all-experts oracle (high capacity)."""
    _assert_ok(multidevice_results, "moe_ep_oracle")


def test_compressed_psum_within_quant_bound(multidevice_results):
    """int8 compressed all-reduce error <= 1 quant step; EF doesn't regress."""
    _assert_ok(multidevice_results, "compressed_psum")


def test_elastic_checkpoint_reshape(multidevice_results):
    """Checkpoint saved on (4,2) restores bit-identically on (2,4) and (8,1)."""
    _assert_ok(multidevice_results, "elastic_checkpoint")


def test_grad_accum_equivalence(multidevice_results):
    """Microbatched accumulation reproduces the single-shot step."""
    _assert_ok(multidevice_results, "grad_accum")


def test_chip_smoke_four_chip_phase(multidevice_results):
    """chip_smoke.py --chips 4's phase: each pull lands on its own device and
    the patterns match numpy (rehearsed on 4 of the host devices)."""
    _assert_ok(multidevice_results, "chip_smoke_phase_c")
