"""environment.json: defaults for the cells' processes, never overrides."""

from benchmarks import spec


def test_defaults_are_set_and_a_machines_own_value_stays(monkeypatch):
    defaults = spec.load_json("environment.json")["defaults"]
    assert defaults and all(isinstance(v, str) for v in defaults.values())
    key = next(iter(defaults))
    monkeypatch.delenv(key, raising=False)
    spec.apply_environment()
    assert spec.os.environ[key] == defaults[key]
    monkeypatch.setenv(key, "the machine's")
    spec.apply_environment()
    assert spec.os.environ[key] == "the machine's"
