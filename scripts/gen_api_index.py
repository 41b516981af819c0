"""Regenerate docs/api.md from the live `bigdl_tpu.nn` registry.

CPU-only; run after adding/removing nn exports:

    JAX_PLATFORMS=cpu python scripts/gen_api_index.py
    JAX_PLATFORMS=cpu python scripts/gen_api_index.py \
        --diff-pyspark [/root/reference]

One row per exported class name, grouped by defining submodule, first
docstring line as the summary; names bound to the same object as
another export are annotated as aliases.

``--diff-pyspark`` audits the PYTHON-facing API against the reference's
pyspark surface (`pyspark/bigdl/nn/layer.py` + `criterion.py` public
classes, name-level): prints every reference class our `bigdl_tpu.nn`
does not export, minus justified infra absences (documented in
docs/interop.md).  Exit 1 when unjustified absences exist.
"""
import inspect
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, __file__.rsplit("/", 2)[0])

from bigdl_tpu import nn                                   # noqa: E402


def first_line(obj):
    doc = inspect.getdoc(obj) or ""
    line = doc.split("\n", 1)[0].strip()
    return line.replace("|", "\\|")


# subsystem packages indexed alongside the nn registry: their public
# classes are the operational API (engines, supervisors, controllers)
# that examples and runbooks reference
SUBSYSTEMS = ("autoscale", "checkpoint", "elastic", "embedding",
              "fleet", "observability", "serving")


def subsystem_sections():
    import importlib
    lines = []
    total = 0
    for pkg in SUBSYSTEMS:
        mod = importlib.import_module(f"bigdl_tpu.{pkg}")
        rows = []
        for name in sorted(dir(mod)):
            if name.startswith("_"):
                continue
            try:
                obj = getattr(mod, name)
            except AttributeError:
                continue
            if not inspect.isclass(obj):
                continue
            home = getattr(obj, "__module__", "")
            if not home.startswith("bigdl_tpu."):
                continue
            rows.append((name, first_line(obj) or "(no docstring)"))
        if not rows:
            continue
        total += len(rows)
        lines += [f"\n## `bigdl_tpu.{pkg}` ({len(rows)})", "",
                  "| class | summary |", "|---|---|"]
        lines += [f"| `{n}` | {s} |" for n, s in rows]
    header = [
        "",
        f"\n# Subsystem API index ({total} classes)",
        "",
        "Public classes re-exported by each subsystem package — the "
        "operational surface (engines, supervisors, controllers, "
        "telemetry) the docs and smokes drive.",
    ]
    return header + lines, total


def main():
    out_path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "docs", "api.md")
    exports = {}
    for name in sorted(dir(nn)):
        if name.startswith("_"):
            continue
        obj = getattr(nn, name)
        if not inspect.isclass(obj):
            continue
        exports[name] = obj

    # group by defining submodule (strip the package prefix)
    groups = {}
    canonical = {}          # id(obj) -> first export name (alias detection)
    for name, obj in exports.items():
        mod = obj.__module__
        short = mod.split("bigdl_tpu.")[-1] if "bigdl_tpu." in mod else mod
        groups.setdefault(short, []).append(name)
        canonical.setdefault(id(obj), name)

    lines = [
        f"# API index: `bigdl_tpu.nn` ({len(exports)} classes)",
        "",
        "Generated from the live registry (`scripts/gen_api_index.py`): "
        "class docstring first lines (reference .scala citations inline); "
        "same-object aliases are marked as such. One entry per exported "
        "name.",
        "",
    ]
    for short in sorted(groups):
        names = sorted(groups[short])
        lines += [f"\n## `{short.replace('nn.', 'nn.', 1)}` "
                  f"({len(names)})", "", "| class | summary |", "|---|---|"]
        for name in names:
            obj = exports[name]
            canon = canonical[id(obj)]
            if canon != name and obj.__name__ != name:
                summary = f"Alias of `{canon}`."
            else:
                summary = first_line(obj) or "(no docstring)"
            lines.append(f"| `{name}` | {summary} |")
    sub_lines, sub_total = subsystem_sections()
    lines += sub_lines
    with open(out_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {os.path.normpath(out_path)}: {len(exports)} nn classes "
          f"({len(groups)} groups) + {sub_total} subsystem classes")


# pyspark classes that are py4j plumbing, not model components — each
# justified in docs/interop.md "pyspark API parity"
_PYSPARK_INFRA = {
    # layer.py's mixin providing the static of()/load JVM-handle helpers;
    # there is no JVM to hand back objects from (our Module.load /
    # utils.serializer covers the functionality)
    "SharedStaticUtils",
}

# py4j gateway machinery with no JAX-side counterpart, per audited file
# (docs/interop.md "pyspark API audit")
_PYSPARK_INFRA_BY_FILE = {
    "util/common.py": {"GatewayWrapper", "JActivity", "JavaCreator",
                       "JavaValue", "SingletonMixin"},
    # Spark-ML Param mixins: our frames take plain ctor args/setters
    "dlframes/dl_classifier.py": {"HasBatchSize", "HasFeatureSize",
                                  "HasLearningRate", "HasMaxEpoch"},
    "nn/keras/layer.py": {"InferShape", "KerasCreator"},
}

# base-Layer METHODS that are py4j/Spark plumbing (no JAX counterpart);
# everything else on pyspark's Layer must exist on our Module
_PYSPARK_LAYER_METHOD_INFRA = {
    "check_input", "convert_output", "from_jvalue", "get_dtype",
    # `name` is a pyspark METHOD; ours is the `name` attribute + get_name
    "name",
    # RDD-based variants: mesh-sharded evaluation goes through
    # DistriOptimizer / Predictor (docs/interop.md)
    "predict_distributed", "predict_class_distributed",
}


def diff_pyspark(ref_root):
    import re
    # classes AND factory callables count (nn.Input is a function here,
    # same call surface as the pyspark class) — but never submodules or
    # constants, which would fake coverage
    ours = {name for name in dir(nn)
            if not name.startswith("_")
            and (inspect.isclass(getattr(nn, name))
                 or inspect.isfunction(getattr(nn, name)))}
    missing = {}
    for rel in ("nn/layer.py", "nn/criterion.py"):
        path = os.path.join(ref_root, "pyspark", "bigdl", rel)
        with open(path) as f:
            src = f.read()
        names = re.findall(r"^class (\w+)", src, re.M)
        exported = [n for n in names if n in ours]
        justified = [n for n in names
                     if n not in ours and n in _PYSPARK_INFRA]
        absent = [n for n in names
                  if n not in ours and n not in _PYSPARK_INFRA]
        print(f"{rel}: {len(exported)}/{len(names)} reference classes "
              f"exported by bigdl_tpu.nn"
              + (f" + {len(justified)} justified infra absence(s): "
                 f"{', '.join(justified)}" if justified else ""))
        if absent:
            missing[rel] = absent
            for n in absent:
                print(f"  MISSING {n}")
    # broader namespaces: vision transforms, keras layers, init methods,
    # util.common, dlframes — class-name level against the live exports
    import importlib
    extra = [
        ("transform/vision/image.py",
         ["bigdl_tpu.data.imageframe", "bigdl_tpu.data.image"]),
        ("nn/keras/layer.py",
         ["bigdl_tpu.keras", "bigdl_tpu.keras.layers",
          "bigdl_tpu.keras.topology"]),
        ("nn/initialization_method.py", ["bigdl_tpu.nn.init",
                                         "bigdl_tpu.nn"]),
        ("util/common.py", ["bigdl_tpu.utils.common", "bigdl_tpu"]),
        ("dlframes/dl_classifier.py", ["bigdl_tpu.frames"]),
        ("dlframes/dl_image_reader.py", ["bigdl_tpu.frames"]),
        ("dlframes/dl_image_transformer.py", ["bigdl_tpu.frames"]),
        ("optim/optimizer.py", ["bigdl_tpu.optim"]),
    ]
    for rel, mods in extra:
        path = os.path.join(ref_root, "pyspark", "bigdl", rel)
        if not os.path.exists(path):
            # a silently skipped namespace would fake a clean audit
            print(f"{rel}: REFERENCE FILE MISSING — audit incomplete")
            missing[rel] = ["<reference file missing>"]
            continue
        with open(path) as f:
            names = re.findall(r"^class (\w+)", f.read(), re.M)
        # getattr (not dir()) so lazy __getattr__ exports (optim's
        # TrainSummary et al) count — but only class/callable values,
        # never submodules or constants (same no-fake-coverage rule as
        # the nn loop above)
        mods_loaded = [importlib.import_module(m) for m in mods]

        def exported(n):
            for m in mods_loaded:
                try:
                    v = getattr(m, n)
                except AttributeError:
                    continue
                if inspect.isclass(v) or callable(v):
                    return True
            return False

        have = {n for n in names if exported(n)}
        infra = _PYSPARK_INFRA_BY_FILE.get(rel, set())
        justified = [n for n in names if n not in have and n in infra]
        absent = [n for n in names if n not in have and n not in infra]
        print(f"{rel}: {len([n for n in names if n in have])}/"
              f"{len(names)} exported"
              + (f" + {len(justified)} justified infra absence(s)"
                 if justified else ""))
        if absent:
            missing[rel] = absent
            for n in absent:
                print(f"  MISSING {n}")

    # base-Layer METHOD surface: everything callable on pyspark's Layer
    # must exist on our Module (minus the py4j plumbing above)
    layer_path = os.path.join(ref_root, "pyspark", "bigdl", "nn",
                              "layer.py")
    with open(layer_path) as f:
        src = f.read()
    m = re.search(r"class Layer\(.*?\n(.*?)\nclass ", src, re.S)
    if m is None:
        # a vacuous pass (methods=set()) would silently disable the
        # whole method-surface gate — fail loudly instead
        print("nn/layer.py: could not locate the Layer class body — "
              "method audit DISABLED; update the regex")
        missing["Layer methods"] = ["<Layer class body not found>"]
        methods = set()
    else:
        methods = set(re.findall(r"\n    def (\w+)\(", m.group(1)))
    from bigdl_tpu.nn import Module
    required = sorted(x for x in methods if not x.startswith("_")
                      and x not in _PYSPARK_LAYER_METHOD_INFRA)
    meth_absent = [x for x in required if x not in dir(Module)]
    if methods:
        print(f"nn/layer.py Layer methods: "
              f"{len(required) - len(meth_absent)}/{len(required)} "
              "required methods on Module "
              f"(+ {len(_PYSPARK_LAYER_METHOD_INFRA)} justified infra)")
    if meth_absent:
        missing["Layer methods"] = meth_absent
        for x in meth_absent:
            print(f"  MISSING method {x}")

    if missing:
        print("pyspark API diff NOT clean")
        return 1
    print("pyspark API diff clean (infra absences justified in "
          "docs/interop.md)")
    return 0


if __name__ == "__main__":
    if "--diff-pyspark" in sys.argv:
        idx = sys.argv.index("--diff-pyspark")
        root = sys.argv[idx + 1] if len(sys.argv) > idx + 1 \
            else "/root/reference"
        sys.exit(diff_pyspark(root))
    main()
