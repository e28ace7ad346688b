"""Tenancy/auth at the front door (riddler role) + the unified config
registry (SURVEY §5.6).

Ref: routerlicious/src/riddler/tenantManager.ts,
protocol-definitions/src/tokens.ts (ITokenClaims JWT),
server config.json nconf layering.
"""

import subprocess
import sys
import time

import pytest

from fluidframework_tpu.config import Config
from fluidframework_tpu.service import LocalServer
from fluidframework_tpu.service.tenants import (
    AuthError,
    SCOPE_READ,
    TenantManager,
    sign_token,
)


# ------------------------------------------------------------------ tokens

def test_valid_token_accepted_and_claims_returned():
    tm = TenantManager()
    tm.register("acme", "s3cret")
    token = sign_token("acme", "doc1", "s3cret", user={"id": "u7"})
    claims = tm.validate(token, "acme", "doc1")
    assert claims["user"]["id"] == "u7"


@pytest.mark.parametrize("case", [
    "wrong_secret", "wrong_tenant", "wrong_doc", "expired", "missing",
    "malformed", "scope", "unknown_tenant",
])
def test_invalid_tokens_rejected(case):
    tm = TenantManager()
    tm.register("acme", "s3cret")
    token = {
        "wrong_secret": lambda: sign_token("acme", "doc1", "WRONG"),
        "wrong_tenant": lambda: sign_token("evil", "doc1", "s3cret"),
        "wrong_doc": lambda: sign_token("acme", "other", "s3cret"),
        "expired": lambda: sign_token("acme", "doc1", "s3cret",
                                      lifetime_s=-10),
        "missing": lambda: None,
        "malformed": lambda: "not.a.token",
        "scope": lambda: sign_token("acme", "doc1", "s3cret",
                                    scopes=(SCOPE_READ,)),
        "unknown_tenant": lambda: sign_token("nobody", "doc1", "x"),
    }[case]()
    tenant = "nobody" if case == "unknown_tenant" else "acme"
    with pytest.raises(AuthError):
        tm.validate(token, tenant, "doc1")


def test_empty_registry_is_open_dev_mode():
    tm = TenantManager()
    assert tm.validate(None, "any", "doc")["scopes"]


def test_server_connect_enforces_tokens():
    tm = TenantManager()
    tm.register("acme", "s3cret")
    server = LocalServer(tenants=tm)
    with pytest.raises(AuthError):
        server.connect("acme", "doc")
    conn = server.connect("acme", "doc",
                          token=sign_token("acme", "doc", "s3cret"))
    assert conn.client_id


def test_invalid_token_rejected_over_the_wire():
    """Cross-process: a front end started with --tenant refuses a bad
    token at connect and admits a signed one."""
    from fluidframework_tpu.driver.network import (
        NetworkDocumentServiceFactory,
    )

    proc = subprocess.Popen(
        [sys.executable, "-m", "fluidframework_tpu.service.front_end",
         "--port", "0", "--tenant", "acme:s3cret"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd="/root/repo")
    try:
        line = proc.stdout.readline().strip()
        port = int(line.rsplit(":", 1)[1])

        no_token = NetworkDocumentServiceFactory("127.0.0.1", port)
        svc = no_token.create_document_service("acme", "doc")
        with pytest.raises(RuntimeError, match="token"):
            svc.connect_to_delta_stream()

        good = NetworkDocumentServiceFactory(
            "127.0.0.1", port,
            token_provider=lambda t, d: sign_token(t, d, "s3cret"))
        conn = good.create_document_service(
            "acme", "doc").connect_to_delta_stream()
        assert conn.client_id
        conn.close()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# ------------------------------------------------------------------ config

def test_config_layering_defaults_overrides_env(monkeypatch):
    base = Config()
    assert base.max_message_size == 16 * 1024
    c = base.with_overrides(max_message_size=1024)
    assert c.max_message_size == 1024 and base.max_message_size == 16 * 1024
    monkeypatch.setenv("FLUID_TPU_CLIENT_TIMEOUT_S", "42.5")
    env = Config.from_env(c)
    assert env.client_timeout_s == 42.5
    assert env.max_message_size == 1024  # explicit layer survives env
    with pytest.raises(KeyError):
        base.with_overrides(nonsense=1)


def test_config_threads_into_service_limits():
    cfg = Config().with_overrides(client_timeout_s=7.0)
    now = [0.0]
    server = LocalServer(clock=lambda: now[0], config=cfg)
    conn = server.connect("t", "doc")
    orderer = server._get_orderer("t", "doc")
    assert orderer.deli._client_timeout == 7.0
    now[0] = 8.0
    server.expire_idle_clients()
    assert conn.client_id not in orderer.deli.clients


def test_config_sets_front_end_message_cap():
    from fluidframework_tpu.service.front_end import NetworkFrontEnd

    cfg = Config().with_overrides(max_message_size=2048)
    fe = NetworkFrontEnd(server=LocalServer(config=cfg))
    assert fe.max_message_size == 2048


def test_storage_and_backfill_rpcs_require_tokens_too():
    """Tenancy covers the REST-role endpoints: a tokenless connection
    must not read a secured doc's op stream or write its storage."""
    from fluidframework_tpu.driver.network import (
        NetworkDocumentServiceFactory,
    )

    proc = subprocess.Popen(
        [sys.executable, "-m", "fluidframework_tpu.service.front_end",
         "--port", "0", "--tenant", "acme:s3cret"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd="/root/repo")
    try:
        line = proc.stdout.readline().strip()
        port = int(line.rsplit(":", 1)[1])

        bare = NetworkDocumentServiceFactory("127.0.0.1", port) \
            .create_document_service("acme", "doc")
        with pytest.raises(RuntimeError, match="token"):
            bare.connect_to_delta_storage().get_deltas(0, 100)
        with pytest.raises(RuntimeError, match="token"):
            bare.connect_to_storage().write_blob(b"sneak")

        def tokens(t, d):
            return sign_token(t, d, "s3cret")

        read_only = NetworkDocumentServiceFactory(
            "127.0.0.1", port,
            token_provider=lambda t, d: sign_token(
                t, d, "s3cret", scopes=(SCOPE_READ,))) \
            .create_document_service("acme", "doc")
        assert read_only.connect_to_delta_storage().get_deltas(0, 10) == []
        with pytest.raises(RuntimeError, match="scope"):
            read_only.connect_to_storage().write_blob(b"nope")

        full = NetworkDocumentServiceFactory(
            "127.0.0.1", port, token_provider=tokens) \
            .create_document_service("acme", "doc")
        blob_id = full.connect_to_storage().write_blob(b"legit")
        assert full.connect_to_storage().read_blob(blob_id) == b"legit"
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_config_empty_env_value_keeps_default(monkeypatch):
    monkeypatch.setenv("FLUID_TPU_APPLIER_KERNEL", "")
    # set-but-empty means unset: the layered default stays
    assert Config.from_env().applier_kernel == "auto"
    monkeypatch.setenv("FLUID_TPU_CLIENT_TIMEOUT_S", "")
    assert Config.from_env().client_timeout_s == Config().client_timeout_s
