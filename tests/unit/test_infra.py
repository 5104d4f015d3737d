"""Infrastructure plane tests.

Mirrors the reference's test style for the API apps (direct handler calls +
artifact inspection); the reference ships no tests for apps/infrastructure,
so coverage here is new."""

from __future__ import annotations

import json

import pytest

from pygrid_tpu.infra import handle_deploy
from pygrid_tpu.infra.cli import main as cli_main
from pygrid_tpu.infra.config import AppConfig, DeployConfig, TpuConfig
from pygrid_tpu.infra.providers import build_provider, server_command
from pygrid_tpu.infra.providers.local import LocalProvider


def _node_config(tmp_path, **kw) -> DeployConfig:
    return DeployConfig(
        app=AppConfig(name="node", id="alice", port=5001,
                      network="http://net:7000"),
        root_dir=str(tmp_path),
        **kw,
    )


def test_server_command_node(tmp_path):
    cmd = server_command(_node_config(tmp_path))
    assert "pygrid_tpu.node" in cmd
    assert ["--id", "alice"] == cmd[cmd.index("--id"):cmd.index("--id") + 2]
    assert "--network" in cmd


def test_gcp_serverfull_renders_tpu_vm(tmp_path):
    provider = build_provider(_node_config(tmp_path))
    artifacts = provider.deploy(apply=False)
    assert artifacts["applied"] is False
    main_tf = json.load(open(f"{artifacts['root_dir']}/main.tf.json"))
    vm = main_tf["resource"]["google_tpu_v2_vm"]["grid_app"]
    assert vm["accelerator_type"] == "v5litepod-8"
    assert "pygrid_tpu.node" in vm["metadata"]["startup-script"]
    fw = main_tf["resource"]["google_compute_firewall"]["grid_ingress"]
    assert {"protocol": "tcp", "ports": ["5001"]} in fw["allow"]


def test_gcp_serverless_renders_cloud_run(tmp_path):
    cfg = _node_config(tmp_path, deployment_type="serverless")
    artifacts = build_provider(cfg).deploy()
    main_tf = json.load(open(f"{artifacts['root_dir']}/main.tf.json"))
    assert "google_cloud_run_v2_service" in main_tf["resource"]
    assert "google_tpu_v2_queued_resource" in main_tf["resource"]


def test_multihost_startup_sets_distributed_env(tmp_path):
    cfg = _node_config(tmp_path)
    cfg.tpu = TpuConfig(num_hosts=4)
    files = build_provider(cfg).render()
    assert "PYGRID_TPU_MULTIHOST=1" in files["startup.sh"]


def test_local_provider_dry_run(tmp_path):
    cfg = _node_config(tmp_path, provider="local")
    provider = build_provider(cfg)
    assert isinstance(provider, LocalProvider)
    result = provider.deploy(apply=False)
    assert result["applied"] is False and "run.sh" in result["files"]


def test_unknown_provider_rejected(tmp_path):
    with pytest.raises(ValueError):
        DeployConfig(provider="ibm")
    # azure graduated from the reference's stub to a working provider
    assert build_provider(
        _node_config(tmp_path, provider="azure")
    ).name == "azure-serverfull"


def test_handle_deploy_roundtrip(tmp_path):
    """The deploy API core: CLI config dict → artifacts on disk (reference
    api/__main__.py:17-40 contract)."""
    payload = _node_config(tmp_path).to_dict()
    result = handle_deploy(payload)
    assert result["message"] == "Deployment successful"
    assert result["provider"] == "gcp"
    assert "main.tf.json" in result["artifacts"]["files"]


def test_cli_direct_dry_run(tmp_path, capsys):
    rc = cli_main([
        "deploy", "--yes", "--direct", "--provider", "gcp", "--app",
        "network", "--port", "7000", "--root-dir", str(tmp_path),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Deployment successful" in out
    configs = list((tmp_path / ".pygrid_tpu" / "cli").glob("config_*.json"))
    assert len(configs) == 1
    assert json.load(open(configs[0]))["app"]["name"] == "network"


def test_azure_serverfull_renders_vm(tmp_path):
    import json as _json

    cfg = _node_config(tmp_path, provider="azure")
    files = build_provider(cfg).render()
    doc = _json.loads(files["main.tf.json"])
    vm = doc["resource"]["azurerm_linux_virtual_machine"]["grid_app"]
    assert vm["size"].startswith("Standard_")
    nsg = doc["resource"]["azurerm_network_security_group"]["grid"]
    assert nsg["security_rule"][0]["destination_port_range"] == str(
        cfg.app.port
    )
    assert "pip install pygrid-tpu" in files["user_data.sh"]


def test_azure_serverless_renders_container_group(tmp_path):
    import json as _json

    from pygrid_tpu.infra.config import DbConfig

    cfg = _node_config(
        tmp_path, provider="azure", deployment_type="serverless",
        db=DbConfig(engine="postgres", url="postgres://u:p@db.corp/grid"),
    )
    files = build_provider(cfg).render()
    doc = _json.loads(files["main.tf.json"])
    grp = doc["resource"]["azurerm_container_group"]["grid_app"]
    container = grp["container"][0]
    assert container["image"] == "${var.image_uri}"
    assert "pygrid_tpu.node" in " ".join(container["commands"])
    assert (
        container["environment_variables"]["DATABASE_URL"]
        == "postgres://u:p@db.corp/grid"
    )
    assert grp["ip_address_type"] == "Public"


def test_checked_in_stacks_match_builders():
    """deploy/<stack>/* are rendered by the live provider builders —
    regeneration must be a no-op (the reference's hand-written HCL can
    drift from its builders; these cannot)."""
    import importlib.util
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[2]
    spec = importlib.util.spec_from_file_location(
        "regenerate", root / "deploy" / "regenerate.py"
    )
    regen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(regen)
    for stack in regen.STACKS:
        rendered = regen.render_stack(stack)
        for fname, contents in rendered.items():
            on_disk = (root / "deploy" / stack / fname).read_text()
            assert on_disk == contents, f"deploy/{stack}/{fname} drifted"


def test_cli_dry_run_flag(tmp_path, capsys):
    """`pygrid-tpu deploy --provider gcp --app node --dry-run` writes the
    terraform configs without applying."""
    rc = cli_main([
        "deploy", "--dry-run", "--provider", "gcp", "--app", "node",
        "--id", "alice", "--root-dir", str(tmp_path),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Deployment successful" in out
    tf = tmp_path / ".pygrid_tpu" / "api" / "gcp-serverfull" / "main.tf.json"
    assert tf.exists()
    doc = json.load(open(tf))
    assert "google_tpu_v2_vm" in doc["resource"]


def test_aws_serverfull_renders_ec2(tmp_path):
    import json as _json

    cfg = _node_config(tmp_path, provider="aws")
    files = build_provider(cfg).render()
    doc = _json.loads(files["main.tf.json"])
    inst = doc["resource"]["aws_instance"]["grid_app"]
    assert "pip install pygrid-tpu" in inst["user_data"]
    sg = doc["resource"]["aws_security_group"]["grid_ingress"]
    assert sg["ingress"][0]["from_port"] == cfg.app.port
    assert doc["provider"]["aws"]["region"]  # zone mapped or default


def test_aws_serverless_renders_lambda_with_efs(tmp_path):
    import json as _json

    cfg = _node_config(tmp_path, provider="aws", deployment_type="serverless")
    files = build_provider(cfg).render()
    doc = _json.loads(files["main.tf.json"])
    fn = doc["resource"]["aws_lambda_function"]["grid_app"]
    assert fn["package_type"] == "Image"
    assert fn["file_system_config"]["local_mount_path"] == "/mnt/pygrid"
    assert "aws_lambda_function_url" in doc["resource"]
    assert "aws_efs_file_system" in doc["resource"]
    # sqlite-on-EFS cannot take concurrent writers: the pin must stay
    assert fn["reserved_concurrent_executions"] == 1


def test_aws_serverless_postgres_lifts_concurrency_pin(tmp_path):
    """With a client-server DB the Lambda scales horizontally: the stack
    provisions in-VPC RDS postgres, drops EFS, and removes the
    reserved-concurrency pin (the reference's Aurora posture,
    deploy/serverless-node/database.tf:1-6)."""
    import json as _json

    from pygrid_tpu.infra.config import DbConfig

    cfg = _node_config(
        tmp_path, provider="aws", deployment_type="serverless",
        db=DbConfig(engine="postgres"),
    )
    files = build_provider(cfg).render()
    doc = _json.loads(files["main.tf.json"])
    fn = doc["resource"]["aws_lambda_function"]["grid_app"]
    assert "reserved_concurrent_executions" not in fn
    assert "file_system_config" not in fn
    assert "aws_efs_file_system" not in doc["resource"]
    rds = doc["resource"]["aws_db_instance"]["grid_db"]
    assert rds["engine"] == "postgres"
    assert doc["variable"]["db_password"]["sensitive"] is True
    url = fn["environment"]["variables"]["DATABASE_URL"]
    assert url.startswith("postgres://") and "grid_db.address" in url
    assert "urlencode(var.db_password)" in url
    # least privilege: the EFS policy grant and NFS ingress die with EFS
    assert "grid_lambda_efs" not in doc["resource"][
        "aws_iam_role_policy_attachment"
    ]
    assert doc["resource"]["aws_security_group"]["grid_efs"]["ingress"] == []


def test_aws_serverless_byo_postgres_url(tmp_path):
    """An explicit postgres:// db.url is wired through verbatim — no RDS
    is provisioned (bring-your-own database)."""
    import json as _json

    from pygrid_tpu.infra.config import DbConfig

    cfg = _node_config(
        tmp_path, provider="aws", deployment_type="serverless",
        db=DbConfig(engine="postgres", url="postgres://u:p@db.corp:5432/grid"),
    )
    files = build_provider(cfg).render()
    doc = _json.loads(files["main.tf.json"])
    fn = doc["resource"]["aws_lambda_function"]["grid_app"]
    assert "reserved_concurrent_executions" not in fn
    assert "aws_db_instance" not in doc["resource"]
    env = fn["environment"]["variables"]
    assert env["DATABASE_URL"] == "postgres://u:p@db.corp:5432/grid"
    # an external DB is unreachable from a default-VPC Lambda: the BYO
    # branch must drop the VPC attachment (and the now-unused app SG)
    assert "vpc_config" not in fn
    assert "grid_efs" not in doc["resource"]["aws_security_group"]
