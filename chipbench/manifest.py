"""BENCHMARK.json and the files it names. No JAX here."""

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent
HERE = pathlib.Path(__file__).resolve().parent


class ManifestError(Exception):
    pass


def load_module(path):
    """Import one file by path: names of configurations and metrics may
    hold '.' and '-', which a dotted import cannot spell."""
    path = pathlib.Path(path).resolve()
    if not path.is_file():
        raise ManifestError(f"missing {path}")
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in path.parent.name + "_" + path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    path = pathlib.Path(path)
    if not path.is_file():
        raise ManifestError(f"missing {path}")
    return json.loads(path.read_text())


class Manifest:
    """BENCHMARK.json of the checkout this package sits in, and the files
    it names by name under chipbench/."""

    def __init__(self):
        self.here = HERE
        self.data = load_json(ROOT / "BENCHMARK.json")

    def cell(self, name):
        for cell in self.data["workloads"]:
            if cell["name"] == name:
                return cell
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json; there are: "
            + ", ".join(c["name"] for c in self.data["workloads"]))

    def metrics(self, kind, cell_name):
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.data[kind]
                if cell_name in m.get("workloads", [cell_name])]

    def config_module(self, cell):
        return load_module(self.here / "configs" / f"{cell['config']}.py")

    def traffic(self, cell):
        return load_json(self.here / "traffic" / f"{cell['traffic']}.json")

    def workload(self, cell):
        return load_json(self.here / "workloads" / f"{cell['name']}.json")

    def layer_reader(self, metric_name):
        return load_module(self.here / "layers" / f"{metric_name}.py").read

    def peaks(self, device_kind):
        table = load_json(self.here / "peaks.json")
        if device_kind not in table:
            raise ManifestError(
                f"device kind {device_kind!r} is not in peaks.json "
                f"({', '.join(k for k in table if k != 'source')})")
        return table[device_kind]
