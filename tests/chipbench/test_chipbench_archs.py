"""Each configuration brings its architecture as a module of its own,
`chipbench/archs/<arch>.py`, found by the file's `arch` key: the
program check, the reference model and the FLOP count all resolve
through it, so a new architecture is new files alone."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, harness, reference, registry

import tiny

SEED = 2**33 + 17

#: readings of the reference before its architectures moved into
#: modules, at SEED with 64-bit mode on as the suite runs: the loss of
#: one agent's batch (2 x 32 tokens, delta 0.1 N(0, 1)), the norm of
#: every leaf of its gradient and of the initial weights
PINNED = {
    "dense": {
        "loss": 4.858180999755859,
        "params": {
            "['blocks']['0_attn']['attn']['wk']": 11.375842094421387,
            "['blocks']['0_attn']['attn']['wo']": 16.010704040527344,
            "['blocks']['0_attn']['attn']['wq']": 11.25654411315918,
            "['blocks']['0_attn']['attn']['wv']": 11.265209197998047,
            "['blocks']['0_attn']['ln1']['scale']": 0.0,
            "['blocks']['0_attn']['ln2']['scale']": 0.0,
            "['blocks']['0_attn']['mlp']['down']": 15.976383209228516,
            "['blocks']['0_attn']['mlp']['gate']": 22.635583877563477,
            "['blocks']['0_attn']['mlp']['up']": 22.60813331604004,
            "['embed']": 3.6163318157196045,
            "['final_norm']['scale']": 0.0,
        },
        "grad": {
            "['blocks']['0_attn']['attn']['wk']": 0.3102988302707672,
            "['blocks']['0_attn']['attn']['wo']": 0.8746997714042664,
            "['blocks']['0_attn']['attn']['wq']": 0.30859553813934326,
            "['blocks']['0_attn']['attn']['wv']": 1.2142232656478882,
            "['blocks']['0_attn']['ln1']['scale']": 0.07794161885976791,
            "['blocks']['0_attn']['ln2']['scale']": 0.0660320371389389,
            "['blocks']['0_attn']['mlp']['down']": 0.9376426339149475,
            "['blocks']['0_attn']['mlp']['gate']": 0.6532431244850159,
            "['blocks']['0_attn']['mlp']['up']": 0.6677842736244202,
            "['embed']": 4.8492751121521,
            "['final_norm']['scale']": 0.07041192054748535,
            "['delta']": 0.4632233870039727,
        },
    },
    "mamba": {
        "loss": 4.8381123542785645,
        "params": {
            "['blocks']['0_mamba1']['ln1']['scale']": 0.0,
            "['blocks']['0_mamba1']['mamba']['A_log']": 186.78146362304688,
            "['blocks']['0_mamba1']['mamba']['D']": 22.627416610717773,
            "['blocks']['0_mamba1']['mamba']['conv_b']": 0.0,
            "['blocks']['0_mamba1']['mamba']['conv_w']": 23.082765579223633,
            "['blocks']['0_mamba1']['mamba']['dt_bias']": 0.0,
            "['blocks']['0_mamba1']['mamba']['dt_proj']": 22.741849899291992,
            "['blocks']['0_mamba1']['mamba']['in_proj']": 31.94457244873047,
            "['blocks']['0_mamba1']['mamba']['norm']": 0.0,
            "['blocks']['0_mamba1']['mamba']['out_proj']": 15.976269721984863,
            "['blocks']['0_mamba1']['mamba']['x_proj']": 6.933021545410156,
            "['embed']": 3.6163318157196045,
            "['final_norm']['scale']": 0.0,
        },
        "grad": {
            "['blocks']['0_mamba1']['ln1']['scale']": 0.065277099609375,
            "['blocks']['0_mamba1']['mamba']['A_log']": 0.009936383925378323,
            "['blocks']['0_mamba1']['mamba']['D']": 0.05031196027994156,
            "['blocks']['0_mamba1']['mamba']['conv_b']": 0.06263317167758942,
            "['blocks']['0_mamba1']['mamba']['conv_w']": 0.11039873212575912,
            "['blocks']['0_mamba1']['mamba']['dt_bias']": 0.030366722494363785,
            "['blocks']['0_mamba1']['mamba']['dt_proj']": 0.0737326443195343,
            "['blocks']['0_mamba1']['mamba']['in_proj']": 1.1571781635284424,
            "['blocks']['0_mamba1']['mamba']['norm']": 0.051524318754673004,
            "['blocks']['0_mamba1']['mamba']['out_proj']": 0.9728524088859558,
            "['blocks']['0_mamba1']['mamba']['x_proj']": 0.8978490829467773,
            "['embed']": 4.141597270965576,
            "['final_norm']['scale']": 0.04543917626142502,
            "['delta']": 0.2618254099182847,
        },
    },
}
#: `flops.round_flops` of each cell before the move
ROUND_FLOPS = {
    "falcon-mamba-7b.gt.m2.s512": 1702417661952.0,
    "granite-8b.gt.m8.b4s64": 5985036926976.0,
}
BENCH = registry.load_benchmark()


def _readings(c):
    pk, dk = reference.seed_keys(SEED)
    x = reference.init_params(pk, c)
    y = {"delta": 0.1 * jax.random.normal(dk, (c["hidden_size"],))}
    b = jax.tree.map(lambda u: u[0], reference.make_data(dk, 1, 2, 32, c["vocab_size"], 7))
    loss = lambda x, y: reference.agent_loss(x, y, b, c)
    gx, gy = jax.grad(loss, argnums=(0, 1))(x, y)

    def norms(t):
        return {k: float(jnp.linalg.norm(v.ravel()))
                for k, v in zip(reference.leaf_paths(t), jax.tree.leaves(t))}

    return {"loss": float(loss(x, y)), "params": norms(x), "grad": {**norms(gx), **norms(gy)}}


@pytest.mark.parametrize("kind", sorted(PINNED))
def test_reference_reads_what_it_read_before_the_move(kind):
    got, want = _readings(tiny.KINDS[kind][0]), PINNED[kind]
    assert got["loss"] == pytest.approx(want["loss"], rel=1e-6)
    for part in ("params", "grad"):
        assert set(got[part]) == set(want[part])
        for leaf, v in want[part].items():
            assert got[part][leaf] == pytest.approx(v, rel=1e-6), (part, leaf)


@pytest.mark.parametrize("workload", sorted(ROUND_FLOPS))
def test_round_flops_of_each_cell_are_what_they_were(workload):
    cell = registry.cell(BENCH, workload)
    assert flops.round_flops(cell["config"], cell["traffic"]) == ROUND_FLOPS[workload]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_every_configuration_names_a_module_with_every_part(entry):
    mod = registry.arch(registry.config(entry)["arch"])
    for part in ("program_args", "program_fields", "init_params", "agent_loss",
                 "forward_flops"):
        assert callable(getattr(mod, part)), part


@pytest.mark.parametrize("shared", ["harness.py", "reference.py", "flops.py"])
def test_shared_files_name_no_layer_kind(shared):
    text = (registry.HERE / shared).read_text()
    kinds = {k for e in BENCH["configs"] for k in registry.config(e)["layer_pattern"]}
    kinds |= {k for cfg, _ in tiny.KINDS.values() for k in cfg["layer_pattern"]}
    for kind in kinds:
        assert f'"{kind}"' not in text, kind
    kernels = {k for cfg, _ in tiny.KINDS.values()
               for k in getattr(registry.arch(cfg["arch"]), "kernel_bytes",
                                lambda c, t: {})(cfg, tiny.TRAFFIC)}
    assert kernels == {"ssm_scan"}
    for kernel in kernels:
        assert kernel not in text, kernel


def test_a_new_architecture_is_new_files_alone(tmp_path, monkeypatch):
    """A third architecture, here granite's module under another name,
    with a configuration file that names it: the program check, the
    reference and the FLOP count all resolve through the new module."""
    before = tiny.digest(registry.HERE)
    archs = tmp_path / "archs"
    archs.mkdir()
    shutil.copy(registry.ARCHS / "granite-8b.py", archs / "granite-copy.py")
    c = dict(tiny.DENSE, name="tiny-copy", arch="granite-copy")
    (tmp_path / "tiny-copy.json").write_text(json.dumps(c))
    c = json.loads((tmp_path / "tiny-copy.json").read_text())
    monkeypatch.setattr(registry, "ARCHS", archs)
    with pytest.raises(FileNotFoundError):
        registry.arch("granite-8b")  # the lookup reads the new directory alone

    cfg = harness.program_config(c)
    assert (cfg.d_model, cfg.num_heads, cfg.pattern) == (256, 4, ("attn",))
    pk, dk = reference.seed_keys(SEED)
    x = reference.init_params(pk, c)
    batch = jax.tree.map(lambda u: u[0], reference.make_data(dk, 1, 2, 32, c["vocab_size"], 7))
    y = {"delta": jnp.zeros((c["hidden_size"],))}
    loss = float(reference.agent_loss(x, y, batch, c))
    fl = flops.round_flops(c, tiny.TRAFFIC)
    monkeypatch.undo()

    d = tiny.DENSE
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(reference.init_params(pk, d))):
        np.testing.assert_array_equal(a, b)
    assert loss == float(reference.agent_loss(x, y, batch, d))
    assert fl == flops.round_flops(d, tiny.TRAFFIC)
    assert tiny.digest(registry.HERE) == before


@pytest.mark.parametrize("entry", ["program_config", "init_params", "agent_loss",
                                   "round_flops"])
def test_unknown_arch_names_the_module_it_looked_for(entry):
    c = dict(tiny.DENSE, arch="no-such-arch")
    calls = {
        "program_config": lambda: harness.program_config(c),
        "init_params": lambda: reference.init_params(jax.random.PRNGKey(0), c),
        "agent_loss": lambda: reference.agent_loss({}, {}, {}, c),
        "round_flops": lambda: flops.round_flops(c, tiny.TRAFFIC),
    }
    with pytest.raises(FileNotFoundError, match=str(registry.ARCHS / "no-such-arch.py")):
        calls[entry]()


def test_program_config_parses_with_the_programs_parser(monkeypatch):
    """A cut flag that the program adds later, with a default that no
    module passes, reaches `_resolve_config` at its default."""
    from repro.launch import train

    parser, resolve = train._parser, train._resolve_config
    seen = []

    def more_flags():
        ap = parser()
        ap.add_argument("--experts-held", type=int, default=8)
        return ap

    def resolve_reading_it(ap, args):
        seen.append(args.experts_held)
        return resolve(ap, args)

    monkeypatch.setattr(train, "_parser", more_flags)
    monkeypatch.setattr(train, "_resolve_config", resolve_reading_it)
    for cfg, _ in tiny.KINDS.values():
        assert harness.program_config(cfg).d_model == cfg["hidden_size"]
    assert seen == [8] * len(tiny.KINDS)
