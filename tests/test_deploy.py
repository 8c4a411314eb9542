"""Install-path validation: the Helm chart
renders to valid k8s objects wired to the image container/Dockerfile
builds, and every CLI flag the pod specs pass actually exists.

No helm binary ships in this environment, so rendering uses a
restricted-subset renderer: the chart deliberately confines itself to
`{{ .Release.Name }}` / `{{ .Values.path }}` substitutions (no
conditionals/loops/helpers), which this test implements faithfully —
the same text `helm template` would produce for these inputs.
"""

from __future__ import annotations

import re
from pathlib import Path

import yaml

REPO = Path(__file__).resolve().parent.parent
CHART = REPO / "deploy" / "helm" / "dynamo-tpu"


def _values() -> dict:
    return yaml.safe_load((CHART / "values.yaml").read_text())


def _lookup(values: dict, dotted: str):
    cur: object = values
    for part in dotted.split("."):
        assert isinstance(cur, dict) and part in cur, (
            f"values.yaml missing {dotted!r} (at {part!r})"
        )
        cur = cur[part]
    return cur


def render(text: str, values: dict, release: str = "test-rel") -> str:
    def sub(m: re.Match) -> str:
        expr = m.group(1).strip()
        if expr == ".Release.Name":
            return release
        assert expr.startswith(".Values."), (
            f"template uses {expr!r} — outside the chart's restricted "
            f"subset; extend the test renderer if this is intentional"
        )
        return str(_lookup(values, expr[len(".Values."):]))

    out = re.sub(r"\{\{([^}]+)\}\}", sub, text)
    assert "{{" not in out and "}}" not in out
    return out


def _rendered_docs(values: dict | None = None) -> list[dict]:
    values = values or _values()
    docs = []
    for tpl in sorted((CHART / "templates").glob("*.yaml")):
        for doc in yaml.safe_load_all(render(tpl.read_text(), values)):
            if doc:
                docs.append(doc)
    return docs


def test_chart_renders_to_valid_k8s_objects():
    docs = _rendered_docs()
    kinds = {(d["kind"], d["metadata"]["name"]) for d in docs}
    for component in ("control-plane", "frontend", "worker"):
        assert ("Deployment", f"test-rel-{component}") in kinds, kinds
    assert ("Service", "test-rel-frontend") in kinds
    for d in docs:
        assert d["apiVersion"] and d["kind"] and d["metadata"]["name"]
        if d["kind"] == "Deployment":
            spec = d["spec"]["template"]["spec"]
            sel = d["spec"]["selector"]["matchLabels"]
            labels = d["spec"]["template"]["metadata"]["labels"]
            assert sel.items() <= labels.items(), (sel, labels)
            assert spec["containers"], d["metadata"]["name"]


def test_chart_image_matches_container_build():
    """Every pod runs the image container/build.sh produces by default,
    and the operator's rendered Deployments default to the same ref —
    one build feeds the whole install path."""
    from dynamo_tpu.operator.resources import DEFAULT_IMAGE

    values = _values()
    expected = f"{values['image']['repository']}:{values['image']['tag']}"
    assert expected == DEFAULT_IMAGE
    build = (REPO / "container" / "build.sh").read_text()
    assert DEFAULT_IMAGE in build
    assert (REPO / "container" / "Dockerfile").exists()
    for d in _rendered_docs(values):
        if d["kind"] != "Deployment":
            continue
        for c in d["spec"]["template"]["spec"]["containers"]:
            assert c["image"] == expected, (d["metadata"]["name"], c["image"])


def test_chart_args_are_real_cli_flags():
    """Chart pods must not pass flags the CLI doesn't have (the failure
    mode that makes an install path rot silently)."""
    cli_src = (REPO / "dynamo_tpu" / "cli.py").read_text()
    known = set(re.findall(r'"(--[a-z][a-z0-9-]*)"', cli_src))
    subcommands = set(re.findall(r'add_parser\(\s*"([a-z-]+)"', cli_src))
    for d in _rendered_docs():
        if d["kind"] != "Deployment":
            continue
        for c in d["spec"]["template"]["spec"]["containers"]:
            args = c.get("args") or []
            assert args[0] in subcommands, args[0]
            for a in args[1:]:
                flag = a.split("=", 1)[0]
                assert flag in known, (
                    f"{d['metadata']['name']}: unknown CLI flag {flag}"
                )


def test_chart_control_plane_addresses_are_consistent():
    """Workers/frontend/planner/metrics dial the control-plane SERVICE the
    chart itself creates, on its configured port."""
    docs = _rendered_docs()
    services = {
        d["metadata"]["name"]: d for d in docs if d["kind"] == "Service"
    }
    cp_port = _values()["controlPlane"]["port"]
    for d in docs:
        if d["kind"] != "Deployment":
            continue
        for c in d["spec"]["template"]["spec"]["containers"]:
            for a in c.get("args") or []:
                if a.startswith("--control-plane="):
                    addr = a.split("=", 1)[1]
                    host, port = addr.rsplit(":", 1)
                    assert host in services, f"{addr}: no such service"
                    assert int(port) == cp_port


def test_worker_graceful_drain_wiring():
    """The worker pod must be drainable without request loss
    (docs/architecture/overload_and_drain.md): readiness probes the
    worker's /health (which 503s while warming OR draining), preStop
    delays SIGTERM so endpoint eviction propagates, and the termination
    grace period covers preStop + the in-process drain budget."""
    values = _values()
    w = values["worker"]
    worker = next(
        d for d in _rendered_docs(values)
        if d["kind"] == "Deployment"
        and d["metadata"]["name"] == "test-rel-worker"
    )
    spec = worker["spec"]["template"]["spec"]
    assert spec["terminationGracePeriodSeconds"] == w[
        "terminationGracePeriodSeconds"
    ]
    c = spec["containers"][0]
    # Readiness rides the new draining state via the worker health port.
    probe = c["readinessProbe"]["httpGet"]
    assert probe["path"] == "/health"
    assert probe["port"] == w["healthPort"]
    assert {"name": "health", "containerPort": w["healthPort"]} in c["ports"]
    # preStop drain hook present and within the grace period.
    pre_stop = c["lifecycle"]["preStop"]["exec"]["command"]
    assert str(w["preStopSleepSeconds"]) in " ".join(pre_stop)
    assert (
        w["preStopSleepSeconds"] + w["drainGraceSeconds"]
        <= w["terminationGracePeriodSeconds"]
    ), "kubelet would SIGKILL mid-drain"
    # The pod passes the drain knobs to the CLI (flag existence is
    # enforced for every arg by test_chart_args_are_real_cli_flags).
    args = " ".join(c["args"])
    assert f"--health-port={w['healthPort']}" in args
    assert f"--drain-grace-s={w['drainGraceSeconds']}" in args


def test_raw_k8s_manifests_parse():
    for f in (REPO / "deploy" / "k8s").glob("*.yaml"):
        for doc in yaml.safe_load_all(f.read_text()):
            if doc:
                assert doc.get("kind"), f
