"""The FedFog round's phase scopes, its phase map and the build-once seam.

``fl/round.py`` traces its stages under ``fedfog.schedule``,
``fedfog.local_train`` and ``fedfog.server``; ``dist.hlo_analysis`` maps
the compiled round's instructions to them in its one walk over the text;
``launch/train._sharded_round_fn`` returns a ``RoundProgram`` and
registers the map with ``repro.obs.spans``. Tiny rwkv6 round on one CPU
device (the 1-device plan of the rules)."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import analyze_hlo, make_rules
from repro.fl import FLConfig
from repro.launch import compile_cache, config_from_args, train
from repro.models import build_model
from repro.obs import spans

SCOPES = {"fedfog.schedule", "fedfog.local_train", "fedfog.server"}
ARGV = ["--scale", "full", "--reduced", "--arch", "rwkv6-1.6b",
        "--slots", "2", "--clients", "4", "--local-steps", "2",
        "--batch-per-slot", "2", "--seq-len", "16", "--pallas-agg"]


class _Round:
    def __init__(self):
        self.args = train.parse_args(ARGV)
        self.cfg = config_from_args(self.args)
        self.model = build_model(self.cfg)
        self.rules = make_rules(None, self.cfg, device_count=1,
                                devices=jax.devices()[:1])
        self.fl_cfg = FLConfig(num_clients=4, slots=2, local_steps=2,
                               use_pallas_agg=True)

    def build(self):
        return train._sharded_round_fn(self.args, self.cfg, self.model,
                                       self.fl_cfg, self.rules, 1.0)

    def state(self, prog):
        return train.init_state(self.model, self.fl_cfg, 0,
                                prog.input_shardings[0][0])

    def batch(self, seed=1):
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        n, a = self.fl_cfg.num_clients, self.args
        gb = self.fl_cfg.slots * a.batch_per_slot * a.local_steps
        u = lambda k, shape: jax.random.uniform(k, shape, minval=0.5,
                                                maxval=1.0)
        return {
            "tokens": jax.random.randint(ks[0], (gb, a.seq_len + 1), 0,
                                         self.cfg.vocab_size),
            "slot_data_sizes": jnp.full((self.fl_cfg.slots,), 50.0),
            "telemetry_cpu": u(ks[1], (n,)),
            "telemetry_mem": u(ks[2], (n,)),
            "telemetry_batt": u(ks[3], (n,)),
            "telemetry_energy": u(ks[4], (n,)),
            "hist": u(ks[5], (n, self.fl_cfg.hist_bins)),
        }


@pytest.fixture(scope="module")
def tiny():
    return _Round()


@pytest.fixture(scope="module")
def program(tiny):
    return tiny.build()


@contextlib.contextmanager
def _unscoped():
    """Trace as a tree without the round's scopes would."""
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.named_scope = real


def _strip_metadata(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return text[text.index("\n\n%"):]  # after the source-location tables


def test_phase_map_names_every_stage_and_covers_the_entry(program):
    text = program.compiled.as_text()
    hlo = analyze_hlo(text)
    assert set(program.phases.values()) == SCOPES
    assert hlo.phases == program.phases and hlo.module == program.module
    assert program.module == "jit_round_fn"
    # Every entry instruction is mapped but those made from parameters
    # and constants alone before any stage runs.
    entry = text[text.index("\nENTRY"):].split("\n}", 1)[0]
    left = {}
    for line in entry.splitlines()[1:]:
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\(",
                     line)
        if m and m.group(1) not in program.phases:
            left[m.group(1)] = m.group(3)
    assert len(left) < 200
    assert set(left.values()) <= {"parameter", "constant", "copy", "bitcast",
                                  "tuple", "get-tuple-element", "fusion"}
    assert all(n.startswith("wrapped_broadcast")
               for n, op in left.items() if op == "fusion"), left
    # Mapped instructions carry their head as a device trace prints it.
    for name in ("while", "fusion"):
        mapped = [n for n in program.phases
                  if hlo.heads[n].rsplit("(", 1)[0].endswith(" " + name)]
        assert mapped, name
    assert set(hlo.heads) == set(hlo.phases)


def test_round_program_is_registered_as_plain_data(program):
    reg = spans.programs()[-1]
    assert reg["module"] == program.module
    assert reg["phases"] == program.phases
    assert set(reg["heads"]) == set(program.phases)
    assert program.compiled not in reg.values()


def test_round_program_forwards_shardings_and_calls_bitwise(tiny, program):
    assert program.input_shardings == program.compiled.input_shardings
    batch = tiny.batch()
    before = spans.stats().get("fedfog.round", spans.Aggregate()).count
    s1, m1 = program(tiny.state(program), batch)
    s2, m2 = program.compiled(tiny.state(program), batch)
    jax.tree.map(np.testing.assert_array_equal, (s1, m1), (s2, m2))
    assert spans.stats()["fedfog.round"].count == before + 1


def test_scopes_change_metadata_only(tiny, program):
    """The round traced without scopes compiles to the same computation
    and computes the same round, bit for bit."""
    with _unscoped():
        bare = tiny.build()
    assert bare.phases == {}
    assert (_strip_metadata(bare.compiled.as_text())
            == _strip_metadata(program.compiled.as_text()))
    batch = tiny.batch(seed=2)
    out = program(tiny.state(program), batch)
    ref = bare(tiny.state(bare), batch)
    jax.tree.map(np.testing.assert_array_equal, out, ref)


def test_phase_map_survives_a_cache_an_unscoped_tree_filled(tiny, tmp_path):
    """An older tree's entry (no scopes, metadata out of the key) is not
    served to the scoped round, whose compile keys on metadata; keyed
    without it, the scoped round would load that entry and map nothing."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(tmp_path), 0, 0)):
        jax.config.update(n, v)
    cc.reset_cache()
    real_key = compile_cache.metadata_in_key
    try:
        compile_cache.metadata_in_key = contextlib.nullcontext
        with _unscoped():
            assert tiny.build().phases == {}
        assert any("round_fn" in f.name for f in tmp_path.iterdir())
        assert tiny.build().phases == {}  # the hazard, without the guard
        compile_cache.metadata_in_key = real_key
        assert set(tiny.build().phases.values()) == SCOPES
    finally:
        compile_cache.metadata_in_key = real_key
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_launcher_reads_each_round_once_into_history_and_tracker(tmp_path):
    import json

    path = tmp_path / "rows.jsonl"
    before = spans.stats()
    run = train.main(["--arch", "rwkv6-1.6b", "--rounds", "2", "--clients",
                      "4", "--slots", "2", "--local-steps", "1",
                      "--batch-per-slot", "2", "--seq-len", "16",
                      "--track", f"jsonl:{path}"])
    after = spans.stats()
    for name in ("fedfog.round.inputs", "fedfog.round", "fedfog.round.read",
                 "fedfog.round.telemetry"):
        was = before.get(name, spans.Aggregate()).count
        assert after[name].count == was + 2, name
    rows = [json.loads(x) for x in path.read_text().splitlines()]
    rows = [r for r in rows if r.get("event") == "round"]
    assert len(rows) == 2 and all(r["round_wall_s"] > 0 for r in rows)
    assert [r["loss"] for r in rows] == [h["loss"] for h in run.history]
    assert all(type(v) is float for h in run.history for v in h.values())
