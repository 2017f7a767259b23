"""Drive script: cls-backed RGW bucket index + numops (round 5).

Boots a mini cluster + the S3 HTTP gateway and drives the index through
the real user surface: PUT/GET/LIST/DELETE over HTTP with the in-OSD
rgw class maintaining the stats header, concurrent writers, multipart,
check/rebuild, and the numops atomic counter.
Run: JAX_PLATFORMS=cpu PYTHONPATH=. python tools/drive_r5_cls.py
"""

import asyncio

from ceph_tpu.rados import MiniCluster
from ceph_tpu.rgw.http import S3Server, auth_header
from ceph_tpu.rgw.store import RGWStore


async def http(addr, method, path, body=b"", headers=None, creds=None):
    host, port = addr
    reader, writer = await asyncio.open_connection(host, port)
    headers = dict(headers or {})
    headers.setdefault("Host", f"{host}:{port}")
    headers["Content-Length"] = str(len(body))
    if creds:
        headers.setdefault("date", "Thu, 01 Jan 2026 00:00:00 GMT")
        access, secret = creds
        # signature covers the path INCLUDING the query string the way
        # the server canonicalizes it
        headers["Authorization"] = auth_header(
            access, secret, method, path, headers
        )
    req = f"{method} {path} HTTP/1.1\r\n"
    req += "".join(f"{k}: {v}\r\n" for k, v in headers.items())
    req += "\r\n"
    writer.write(req.encode() + body)
    await writer.drain()
    status_line = await reader.readline()
    status = int(status_line.split()[1])
    hdrs = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b""):
            break
        k, _, v = line.decode().partition(":")
        hdrs[k.strip().lower()] = v.strip()
    data = b""
    if "content-length" in hdrs:
        data = await reader.readexactly(int(hdrs["content-length"]))
    writer.close()
    return status, hdrs, data


async def main():
    async with MiniCluster(n_osds=3) as cluster:
        cl = await cluster.client()
        store = await RGWStore.create(cl)
        user = await store.create_user("alice", "Alice")
        creds = (user["access_key"], user["secret_key"])
        server = S3Server(store)
        url = await server.start()
        host, port = url.rsplit(":", 1)[0].replace("http://", ""), \
            int(url.rsplit(":", 1)[1])
        addr = (host, port)

        st, _, _ = await http(addr, "PUT", "/shots", creds=creds)
        assert st in (200, 201), st
        # concurrent PUTs through the gateway: header must stay exact
        await asyncio.gather(*(
            http(addr, "PUT", f"/shots/img{i:02d}.bin",
                 body=bytes([i]) * 100, creds=creds)
            for i in range(20)
        ))
        stats = await store.bucket_stats("shots")
        assert stats["num_objects"] == 20, stats
        assert stats["size_bytes"] == 2000, stats
        print("  ok: 20 concurrent HTTP PUTs; header exact:", stats)

        chk = await store.check_index("shots")
        assert chk["consistent"], chk
        print("  ok: check_index consistent")

        st, _, body = await http(
            addr, "GET", "/shots?prefix=img&max-keys=7", creds=creds
        )
        import json as _json

        listing = _json.loads(body)
        assert st == 200 and len(listing["contents"]) == 7, listing
        assert listing["truncated"] is True
        print("  ok: HTTP paged listing honors max-keys via cls list")

        st, _, data = await http(
            addr, "GET", "/shots/img05.bin", creds=creds
        )
        assert st == 200 and data == bytes([5]) * 100
        st, _, _ = await http(
            addr, "DELETE", "/shots/img05.bin", creds=creds
        )
        assert st in (200, 204)
        stats = await store.bucket_stats("shots")
        assert stats["num_objects"] == 19 and stats["size_bytes"] == 1900
        print("  ok: GET + DELETE keep the header in lockstep")

        # numops: concurrent atomic counter via the rados surface
        await cl.create_pool("ctrs", "replicated")
        io = cl.io_ctx("ctrs")
        await asyncio.gather(*(
            io.exec("hits", "numops", "add", {"key": "n", "value": 1})
            for _ in range(64)
        ))
        out = await io.exec("hits", "numops", "add",
                            {"key": "n", "value": 0})
        assert out["value"] == "64", out
        print("  ok: 64 concurrent numops.add == 64")
        await server.stop()
    print("PASS: cls-backed index + numops end-to-end over HTTP")


if __name__ == "__main__":
    asyncio.run(main())
