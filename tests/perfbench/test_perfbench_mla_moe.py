"""The DeepSeek-V3 family of the benchmark (``families/galaxy_mla_moe.py``)
at a tiny size on the CPU: its reference against the repository's, a
whole run that comes out correct while its lower-precision control does
not, the near-tie rule on a planted flip, its counts by hand, and the
readers of its three metrics on a synthetic trace."""
import copy
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import check, progspans, run  # noqa: E402

SEED = 2_147_483_659
CONF = dict(run.load_json(os.path.join(
    ROOT, "perfbench", "configs", "moonlight-16b-a3b-ep4.json")),
    name="tiny-mla", hidden_size=64, num_attention_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=4,
    n_routed_experts_published=8, num_experts_per_tok=2, num_hidden_layers=3,
    vocab_size=512, max_position_embeddings=64,
    check={"logit_err": 0.01, "control": "float8_e4m3fn",
           "route_margin": 1e-5})
MIX = {"kind": "single", "clients": 4, "prompt_tokens": [8, 16],
       "output_tokens": [2, 8],
       "engine": {"max_batch": 4, "max_len": 32, "page_size": 16,
                  "prefix_cache": False}}


@pytest.fixture(scope="module")
def family():
    return run.load_module(os.path.join(ROOT, "perfbench", "families",
                                        "galaxy_mla_moe.py"))


@pytest.fixture(scope="module")
def weights(family):
    import jax
    return family.make_weights(CONF, SEED, jax.devices())


def _repo_reference(family, weights, seq):
    import jax
    from repro.models import deepseek_v3

    cfg = family._program_config(CONF)
    return np.asarray(jax.jit(deepseek_v3.forward, static_argnums=0)(
        cfg, weights["layers"], weights["vocab"], np.asarray(seq)))


def test_family_reference_matches_the_repositorys(family, weights,
                                                  monkeypatch):
    monkeypatch.setattr(family, "_RECORDER", None)
    seq = np.random.default_rng(1).integers(0, CONF["vocab_size"], 23)
    tokens = np.zeros((2, 40), np.int32)
    tokens[1, :23] = seq
    rows = np.stack([np.ones(23, np.int32), np.arange(23)], 1)
    got = family.reference_logits(CONF, weights, tokens, rows)
    want = _repo_reference(family, weights, seq)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * want.std(axis=1).min())


def test_sound_run_is_correct_and_the_control_is_not(monkeypatch, capsys):
    """One run with the float8 control: the program's own readings pass
    with room, the control's fail by far; the routing of every sampled
    request was matched and its near-ties counted."""
    import jax

    monkeypatch.setattr(check, "KEEP_SHARE", 1.0)
    spec = run.benchmark()
    res = run.run_cell(CONF, MIX, seed=SEED, seconds=0.25, trace=False,
                       devices=jax.devices(), peaks=None,
                       e2e=spec["end_to_end"], per_layer=[], control=True)
    limit = CONF["check"]["logit_err"]
    assert res["checks"]["program_logit_err"]["value"] < limit / 30
    assert res["checks"]["logit_err"]["value"] > 30 * limit
    assert not res["correct"]
    assert res["checks"]["unmatched"]["value"] == 0
    err = capsys.readouterr().err
    assert "0 sequences with no recorded routing" in err
    assert "moe: assignments per held expert" in err


def test_op_paths_name_the_ops_the_decode_program_ran(family, weights,
                                                     tmp_path, monkeypatch):
    """The decode program compiled again from the recorded call gives the
    instructions a profile of that call names, with the block's scopes."""
    import glob

    import jax
    from jax.profiler import ProfileData

    rec = family.make_system(CONF, weights, jax.devices())
    pool = rec.make_pool(9, 16)
    slots = 2
    tokens = np.ones((slots, 1), np.int32)
    table = np.arange(1, 1 + 2 * slots, dtype=np.int32).reshape(slots, 2)
    positions = np.array([3, 17], np.int32)
    _, pool = rec.decode_paged(tokens, pool, table, positions)
    jax.profiler.start_trace(str(tmp_path))
    _, pool = jax.block_until_ready(
        rec.decode_paged(tokens, pool, table, positions))
    jax.profiler.stop_trace()
    ran = set()
    for f in glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True):
        for plane in ProfileData.from_file(f).planes:
            for line in plane.lines:
                ran |= {e.name for e in line.events
                        if dict(e.stats).get("hlo_module") == "jit_decode"}
    paths = family.op_paths("decode")
    assert ran and len(ran & set(paths)) >= 0.9 * len(ran)
    for scope in ("moe.experts", "mla.attention"):
        assert any(f"/{scope}/" in paths[n] for n in ran & set(paths))
    assert family.op_paths("prefill") is None


def test_instruction_paths_give_unscoped_kernels_their_consumers_scope():
    """A TPU ``ragged-dot`` kernel (``op_name`` without a name stack) and a
    weight prefetch (no ``op_name``) take the stack of the nearest
    instruction that consumes them; one with no such consumer keeps its
    own ``op_name`` or is left out."""
    from perfbench import opscopes

    text = """
ENTRY %main.1 (p.0: f32[16,2048,1408], p.1: f32[96,2048]) -> f32[96,1408] {
  %p.0 = f32[16,2048,1408]{2,1,0} parameter(0)
  %p.1 = f32[96,2048]{1,0} parameter(1)
  %slice-done = f32[2048,4,192]{0,2,1} slice-done(%slice-start)
  %custom-call.24 = f32[2048,16,192]{0,2,1} custom-call(%slice-done), custom_call_target="ConcatBitcast"
  %fusion.64 = f32[16,16,192]{2,0,1} fusion(%p.1, %custom-call.24), kind=kOutput, calls=%fc.1, metadata={op_name="jit(decode)/mla.latent/dot_general" stack_frame_id=3}
  %fusion.4 = f32[96,2048]{1,0} fusion(%p.1), kind=kLoop, calls=%fc.2, metadata={op_name="jit(decode)/moe.experts/gather"}
  %ragged-dot-none.1 = f32[96,1408]{1,0} custom-call(%fusion.4, %p.0), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.2 = f32[96,1408]{1,0} custom-call(%fusion.4, %p.0), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %multiply_multiply_fusion = f32[96,1408]{1,0} fusion(%ragged-dot-none.1, %ragged-dot-none.2), kind=kLoop, calls=%fc.3, metadata={op_name="jit(decode)/moe.experts/mul"}
}
"""
    paths = opscopes.instruction_paths(text)
    assert paths["ragged-dot-none.1"] == "jit(decode)/moe.experts/mul"
    assert paths["ragged-dot-none.2"] == "jit(decode)/moe.experts/mul"
    assert paths["custom-call.24"] == "jit(decode)/mla.latent/dot_general"
    assert paths["slice-done"] == "jit(decode)/mla.latent/dot_general"
    assert paths["fusion.4"] == "jit(decode)/moe.experts/gather"
    assert "multiply_multiply_fusion" in paths and "p.0" in paths
    assert "p.1" in paths
    unscoped = text.replace('op_name="jit(decode)/moe.experts/mul"',
                            'op_name="mul"')
    assert opscopes.instruction_paths(unscoped)["ragged-dot-none.1"] \
        == "ragged-dot-none"


class Planted:
    """A recorder whose program chose, at one (layer, position), the 7th
    expert in place of the 6th; unknown (-1) everywhere else."""

    def __init__(self, routes):
        self.routes = routes
        self.ex = None

    def program_routes(self, seq, prompt, n):
        return self.routes[:, :n]


def _first_moe_keys(family, weights, seq):
    """Router keys (score + bias) of the first MoE layer over ``seq``, from
    the repository's reference."""
    import jax
    from repro.models import deepseek_v3

    cfg = family._program_config(CONF)
    p0, p1 = weights["layers"][:2]
    with jax.default_matmul_precision("highest"):
        x = deepseek_v3.layer(cfg, p0, weights["vocab"]["table"][seq])
        h = x + deepseek_v3.attention(
            cfg, p1, deepseek_v3.rms_norm(x, p1["attn_norm"], cfg.eps))
        xn = deepseek_v3.rms_norm(h, p1["ffn_norm"], cfg.eps)
        return np.asarray(jax.nn.sigmoid(xn @ p1["router"])
                          + p1["router_bias"])


def _planted(family, weights, monkeypatch, seq, at, route):
    """Reference logits at positions ``at`` .. of ``seq`` under a route
    limit, with a recorder whose program chose ``route`` at ``at`` in the
    first MoE layer (unknown everywhere else)."""
    k, n = CONF["num_experts_per_tok"], len(seq)
    routes = np.full((CONF["num_hidden_layers"] - 1, n, k), -1, np.int32)
    routes[0, at] = route
    monkeypatch.setattr(family, "_RECORDER", Planted(routes))
    tokens = seq[None].astype(np.int32)
    rows = np.stack([np.zeros(n - at, np.int32), np.arange(at, n)], 1)

    def logits(margin_limit):
        conf = copy.deepcopy(CONF)
        conf["check"]["route_margin"] = float(margin_limit)
        return family.reference_logits(conf, weights, tokens, rows)

    return logits


def test_near_tie_rule_follows_a_planted_flip_under_the_margin_only(
        family, weights, monkeypatch):
    """Where the reference's own k-th to (k+1)-th key margin is under
    ``route_margin`` it follows the program's choice; over it, it routes
    by itself, so a program that chose otherwise fails ``logit_err``."""
    k, n = CONF["num_experts_per_tok"], 12
    seq = np.random.default_rng(4).integers(0, CONF["vocab_size"], n)
    keys = _first_moe_keys(family, weights, seq)
    order = np.argsort(-keys, -1)
    held = np.asarray(weights["layers"][1]["held"])
    # the last position whose k-th and (k+1)-th experts differ in being held
    at = max(i for i in range(n)
             if np.isin(order[i, k - 1], held) != np.isin(order[i, k], held))
    margin = keys[at, order[at, k - 1]] - keys[at, order[at, k]]
    logits = _planted(family, weights, monkeypatch, seq, at,
                      np.r_[order[at, :k - 1], order[at, k]])
    followed, own = logits(2 * margin), logits(margin / 2)
    served = followed.argmax(1)
    # the program that chose the flipped expert, judged by either reference
    assert check.readings(followed, followed, served)["logit_err"] == 0
    assert check.readings(own, followed, served)["logit_err"] > \
        CONF["check"]["logit_err"]
    want = _repo_reference(family, weights, seq)[at:]
    np.testing.assert_allclose(own, want, rtol=0,
                               atol=1e-4 * want.std(axis=1).min())


def test_near_tie_rule_does_not_follow_a_far_expert_at_a_near_tie(
        family, weights, monkeypatch):
    """A route the program chose is followed only when it is itself a
    near-tie: an expert far down the reference's keys in place of its
    k-th is not followed at a position whose own k-th to (k+1)-th margin
    is under the limit, and fails ``logit_err``; it is followed once the
    limit covers its distance."""
    k, n = CONF["num_experts_per_tok"], 12
    seq = np.random.default_rng(4).integers(0, CONF["vocab_size"], n)
    keys = _first_moe_keys(family, weights, seq)
    order = np.argsort(-keys, -1)
    held = np.asarray(weights["layers"][1]["held"])
    rows = np.arange(n)
    kth = keys[rows, order[:, k - 1]]
    margin = kth - keys[rows, order[:, k]]
    def far(i):
        """The last expert in key order past the (k+1)-th whose being held
        differs from the k-th's, so the swap changes the held share's
        sum."""
        js = [j for j in range(k + 1, order.shape[1])
              if np.isin(order[i, j], held) != np.isin(order[i, k - 1], held)]
        return order[i, js[-1]] if js else order[i, k - 1]

    distance = np.array([kth[i] - keys[i, far(i)] for i in rows])
    at = max(i for i in rows if distance[i] > 4 * margin[i])
    logits = _planted(family, weights, monkeypatch, seq, at,
                      np.r_[order[at, :k - 1], far(at)])
    limit = np.sqrt(margin[at] * distance[at])  # over the margin, under
    own = logits(limit)
    followed = logits(2 * distance[at])
    served = followed.argmax(1)
    assert check.readings(own, followed, served)["logit_err"] > \
        CONF["check"]["logit_err"]
    want = _repo_reference(family, weights, seq)[at:]
    np.testing.assert_allclose(own, want, rtol=0,
                               atol=1e-4 * want.std(axis=1).min())


def test_counts_by_hand(family):
    z = CONF
    # the absorbed core: (2 rank + rope) lanes a head, two flops a MAC
    assert family.mla_core_flops(z, 10) == 2 * 3 * 4 * (2 * 32 + 8) * 10
    # 576-lane analogue: rank + rope lanes of 4 B per layer
    assert family.mla_core_bytes(z, 10) == 3 * (32 + 8) * 4 * 10
    assert family.expert_flops(z, 5) == 6 * 64 * 32 * 5
    assert family.expert_bytes(z, 2) == 3 * 64 * 32 * 4 * 2
    assert family.attention_flops(z, 9) == family.mla_core_flops(z, 10)


def test_weight_bytes_are_the_weights(family):
    import jax

    shapes = jax.eval_shape(lambda: family._init(jax.random.PRNGKey(0), CONF))
    held = sum(p["held"].size * 4 for p in shapes["layers"] if "held" in p)
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert family.weight_bytes(CONF) == total - held


# --- the three readers on a synthetic trace ------------------------------------

US = 1_000_000  # picoseconds per microsecond


def _plane(pid, name, lines):
    """An xplane in text form: ``lines`` of (name, [(event, start us, dur
    us, {stat: int or str})])."""
    names = sorted({e[0] for _, evs in lines for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    keys = sorted({k for _, evs in lines for e in evs for k in e[3]})
    stat = {k: i + 1 for i, k in enumerate(keys)}
    value = lambda v: (f'str_value: "{v}"' if isinstance(v, str)
                       else f"int64_value: {v}")
    body = []
    for lid, (ln, evs) in enumerate(lines, 1):
        body.append(f'lines {{ id: {lid} name: "{ln}" timestamp_ns: 0')
        for ev, start, dur, stats in evs:
            st = " ".join(f"stats {{ metadata_id: {stat[k]} {value(v)} }}"
                          for k, v in stats.items())
            body.append(f"  events {{ metadata_id: {meta[ev]} offset_ps: "
                        f"{start * US} duration_ps: {dur * US} {st} }}")
        body.append("}")
    body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
             for n, i in meta.items()]
    body += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
             for n, i in stat.items()]
    return f'planes {{ id: {pid} name: "{name}"\n' + "\n".join(body) + "\n}"


def _op(name, start, dur):
    """A TPU op event: named by its whole HLO instruction, no name stack."""
    return (f"%{name} = f32[8,128]{{1,0}} fusion(%p.1), kind=kLoop", start,
            dur, {})


def _paths(scoped=True):
    """The compiled decode program's instruction paths, as ``op_paths``
    gives them; without scopes, every op's path lacks them."""
    s = (lambda x: x) if scoped else (lambda x: "body")
    return {"fusion.1": f"jit(decode)/jit(main)/shard_map/{s('moe.experts')}"
                        "/dot_general",
            "fusion.2": f"jit(decode)/jit(main)/shard_map/{s('mla.attention')}"
                        "/dot_general",
            "fusion.3": "jit(decode)/jit(main)/add"}


def _trace():
    """Two decode runs (10-60, 100-150 us) in a 0-200 us window, each with
    20 us of ``fusion.1`` (``moe.experts``), 10 of ``fusion.2``
    (``mla.attention``) and 20 of ``fusion.3``; a prefill run at 160-190 us
    with 20 us of an op named ``fusion.1`` too."""
    ops = []
    for at in (10, 100):
        ops += [_op("fusion.1", at, 20), _op("fusion.2", at + 20, 10),
                _op("fusion.3", at + 30, 20)]
    ops.append(_op("fusion.1", 160, 20))
    device = [("XLA Modules", [("jit_decode(1)", 10, 50, {}),
                               ("jit_decode(1)", 100, 50, {}),
                               ("jit_prefill(2)", 160, 30, {})]),
              ("XLA Ops", ops)]
    host = [("perfbench.window", 0, 200, {}),
            ("galaxy.decode", 5, 3, {"call": 7}),
            ("galaxy.decode", 95, 3, {"call": 8})]
    return "\n".join([_plane(1, "/device:TPU:0", device),
                      _plane(9, "/host:CPU", [("python", host)])])


class Steps:
    """A recorder stand-in: each decode call's expert ids, block table and
    positions."""

    def __init__(self, ex, decodes):
        self.ex, self.decodes = ex, decodes

    def decode_host(self):
        return (np.stack([np.asarray(bt)[:, 0] for bt, _, _ in self.decodes]),
                np.stack([np.asarray(p) for _, p, _ in self.decodes]))


def _ctx(tmp_path, monkeypatch, family, text):
    from jax.profiler import ProfileData
    from perfbench import xtrace

    monkeypatch.setattr(progspans, "TRACE_DIR", str(tmp_path))
    d = tmp_path / "tiny-mla-7" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    tr = xtrace.from_profile(ProfileData.from_text_proto(text))
    lo, hi = tr.window()
    return SimpleNamespace(conf=CONF, family=family, chips=1, trace=tr,
                           lo=lo, hi=hi, peaks={"bf16_flops": 1e12,
                                                "hbm_bytes_per_s": 1e9})


def _reader(name):
    return run.load_module(os.path.join(ROOT, "perfbench", "metrics",
                                        name + ".py"))


def test_expert_and_latent_readers_on_a_synthetic_trace(tmp_path,
                                                        monkeypatch, family):
    """Two MoE layers holding experts 0-3 of 8, two slots: decode call 7
    routes slot 0 to (0, 5) and slot 1 to (0, 1) in both layers, slot 1
    live at position 9; call 8 routes both to (6, 7), nothing held, slot 0
    live at position 4."""
    layer = SimpleNamespace(is_moe=True, p={"held": np.arange(4)})
    ex = SimpleNamespace(layers=[SimpleNamespace(is_moe=False), layer, layer])
    routes7 = np.array([[[0, 5], [0, 1]]] * 2)
    routes8 = np.array([[[6, 7], [6, 7]]] * 2)
    decodes = [(np.zeros((2, 1), int), np.zeros(2, int), routes8)] * 7 + [
        (np.array([[0], [3]]), np.array([0, 9]), routes7),
        (np.array([[5], [0]]), np.array([4, 0]), routes8)]
    monkeypatch.setattr(family, "_RECORDER", Steps(ex, decodes))
    monkeypatch.setattr(family, "op_paths", lambda program: _paths())
    ctx = _ctx(tmp_path, monkeypatch, family, _trace())
    # held hits: call 7 (0, 0, 1) in each layer -> 6 pairs over experts
    # {0, 1} in 2 layers -> 4 active; call 8 none; live positions 10, 5
    assert family.decode_window(ctx) == [(6, 4, 10), (0, 0, 5)]
    assert _reader("moe_expert_ms_per_step").read(ctx) == pytest.approx(20e-3)
    flops = [family.expert_flops(CONF, 6), 0.0]
    byts = [family.expert_bytes(CONF, 4), 0.0]
    least = [max(f / 1e12, b / 1e9) for f, b in zip(flops, byts)]
    assert _reader("moe_expert_roofline").read(ctx) == pytest.approx(
        100 * sum(least) / 40e-6)
    least = [max(family.mla_core_flops(CONF, n) / 1e12,
                 family.mla_core_bytes(CONF, n) / 1e9) for n in (10, 5)]
    assert _reader("mla_decode_roofline").read(ctx) == pytest.approx(
        100 * sum(least) / 20e-6)


def test_readers_refuse_runs_without_scopes_and_need_a_record(
        tmp_path, monkeypatch, family):
    """Decode runs whose ops carry no such scope, or whose op names miss the
    compiled text, are an error, not a silent gap; a run without the
    family's record reads nothing."""
    layer = SimpleNamespace(is_moe=True, p={"held": np.arange(4)})
    ex = SimpleNamespace(layers=[layer])
    decodes = [(np.zeros((2, 1), int), np.zeros(2, int),
                np.zeros((1, 2, 2), int))] * 9
    monkeypatch.setattr(family, "_RECORDER", Steps(ex, decodes))
    ctx = _ctx(tmp_path, monkeypatch, family, _trace())
    names = ("moe_expert_ms_per_step", "moe_expert_roofline",
             "mla_decode_roofline")
    monkeypatch.setattr(family, "op_paths",
                        lambda program: _paths(scoped=False))
    for name in names:
        with pytest.raises(RuntimeError, match="carries the scope"):
            _reader(name).read(ctx)
    monkeypatch.setattr(family, "op_paths", lambda program: {
        k: v for k, v in _paths().items() if k != "fusion.3"})
    for name in names:
        with pytest.raises(RuntimeError, match="not the program that ran"):
            _reader(name).read(ctx)
    monkeypatch.undo()  # the family's own op_paths, and no record
    monkeypatch.setattr(family, "_RECORDER", None)
    ctx = _ctx(tmp_path / "b", monkeypatch, family, _trace())
    for name in names:
        assert _reader(name).read(ctx) is None
