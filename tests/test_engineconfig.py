"""EngineConfig and the constructor-injection API: the engine is named
where the torus is built, unnamed means numpy, ``mask_client=``
injects a client, and clones inherit the config but not the client."""
import pytest

from repro.core.allocator import make_policy
from repro.core.engineconfig import EngineConfig
from repro.core.maskquery import InlineMaskClient, resolve_mask_client
from repro.core.reconfig import ReconfigTorus
from repro.core.torus import StaticTorus
from repro.kernels.fitmask import ops


# ------------------------------------------------------------- coerce
def test_coerce_spellings():
    assert EngineConfig.coerce(None) == EngineConfig()
    assert EngineConfig.coerce("ref").engine == "ref"
    cfg = EngineConfig(engine="numpy", fleet_size=4)
    assert EngineConfig.coerce(cfg) is cfg
    with pytest.raises(TypeError):
        EngineConfig.coerce(42)
    assert EngineConfig().resolve_name() == "numpy"
    assert EngineConfig(engine="ref").resolve_name() == "ref"


def test_alias_folding_and_unknown_names():
    for name in ("bogus", "kernel", "auto"):
        with pytest.raises(KeyError):
            EngineConfig(engine=name).resolve_name()
        with pytest.raises(KeyError):
            StaticTorus((4, 4, 4), engine=name)


def test_mask_client_resolution():
    assert resolve_mask_client(None) is None            # numpy: host path
    assert resolve_mask_client("numpy") is None
    c = resolve_mask_client("ref")
    assert isinstance(c, InlineMaskClient)
    # every inline client answers from the registry's one instance
    assert c.engine is ops.get_engine("ref")
    assert resolve_mask_client(EngineConfig(engine="ref")).engine is c.engine


@pytest.mark.parametrize("cfg,fingerprint", [
    ({}, "6a37962768e6e78d"),
    ({"policy": "rfold", "policy_kw": {"num_xpus": 4096, "cube_n": 4},
      "backfill": True, "engine": "pallas"}, "22598110423eebf3")])
def test_scheduler_fingerprint_keeps_journal_names(cfg, fingerprint):
    """The daemon's journal and snapshot names hash the engine config's
    fields: these values were written by earlier releases, and a daemon
    that computes another name would not find its journal."""
    from repro.serve.scheduler import SchedulerConfig
    assert SchedulerConfig(**cfg).fingerprint() == fingerprint


# ------------------------------------------------- constructor injection
def test_torus_constructor_injection():
    client = InlineMaskClient("ref")
    t = StaticTorus((4, 4, 4), engine="ref", mask_client=client)
    assert t.engine_config.engine == "ref"
    assert t.mask_client is client
    r = ReconfigTorus(num_xpus=64, cube_n=4, engine=EngineConfig("ref"))
    assert r.engine_config.engine == "ref"
    assert r.mask_client.engine is ops.get_engine("ref")
    assert StaticTorus((4, 4, 4)).mask_client is None
    assert ReconfigTorus(num_xpus=64, cube_n=4).mask_client is None


def test_policy_clones_inherit_engine_config():
    broker = InlineMaskClient(ops.get_engine("jax"))
    pol = make_policy("rfold", num_xpus=64, cube_n=4, mask_client=broker,
                      engine=EngineConfig(engine="ref", fleet_size=2))
    assert pol.cluster.mask_client is broker
    clone = pol.empty_clone()
    assert clone.cluster.engine_config == pol.cluster.engine_config
    # probes never share the injected client: they take the engine's own
    assert clone.cluster.mask_client is not broker
    assert clone.cluster.mask_client.engine is ops.get_engine("ref")

    static = make_policy("folding", dims=(4, 4, 4), engine="ref")
    assert static.empty_clone().torus.engine_config.engine == "ref"


# ------------------------------------------------- removed spellings
def test_removed_fitmask_engine_kwarg_raises():
    with pytest.raises(TypeError):
        make_policy("folding", dims=(4, 4, 4), fitmask_engine="jax")


def test_removed_alias_is_unknown():
    with pytest.raises(KeyError):
        ops.get_engine("auto")


def test_removed_env_var_is_ignored(monkeypatch):
    from repro.core.geometry import JobShape
    monkeypatch.setenv("REPRO_FITMASK_ENGINE", "pallas")
    pol = make_policy("folding", dims=(6, 6, 6))
    assert pol.torus.mask_client is None             # numpy host path
    assert pol.try_place(1, JobShape((2, 2, 2))) is not None
    assert not pol.torus._box_masks                  # no engine cache
