#!/bin/bash
# Builds a runnable copy of the read-only reference at /tmp/refvizier:
#  - copies the tree (the original at /root/reference must stay untouched),
#  - compiles its protos against the googleapis protos shipped inside
#    site-packages (no network),
#  - patches vizier/pyvizier/converters/__init__.py to tolerate the absence
#    of equinox/tfp (those deps are not in this image and installs are
#    banned, so the reference's GP stack cannot run; random / grid /
#    quasi-random / NSGA2 / harmonica / eagle all work).
# Used by parity_suite.py to measure the reference behaviorally
# (BASELINE.md: the reference publishes no numbers, so it must be
# run as its own baseline).
set -e

REF=${1:-/root/reference}
DST=${2:-/tmp/refvizier}
SP=$(python -c "import site; print(site.getsitepackages()[0])")

rm -rf "$DST"
mkdir -p "$DST"
cp -r "$REF/vizier" "$DST/"

# google/longrunning ships its proto under a different filename.
INC=/tmp/protoinc
mkdir -p "$INC/google/longrunning"
cp "$SP/google/longrunning/operations_proto.proto" \
   "$INC/google/longrunning/operations.proto"

export DST
cd "$DST/vizier/_src/service"
protoc -I. -I"$INC" -I"$SP" --python_out=. \
  key_value.proto study.proto vizier_oss.proto \
  vizier_service.proto pythia_service.proto

python - << 'EOF'
import os
import pathlib

DST = pathlib.Path(os.environ['DST'])

# grpcio-tools (the *_pb2_grpc generator) is absent from this image; emit
# descriptor-driven shims that provide the same Stub / Servicer /
# add_*_to_server surface the reference's service modules import.
_SHIM = '''"""Descriptor-driven stand-in for the grpcio-tools generated module."""
import grpc
from vizier._src.service import {pb2} as _pb2

try:
    from google.protobuf import message_factory

    def _cls(desc):
        return message_factory.GetMessageClass(desc)
except (ImportError, AttributeError):  # protobuf < 4
    from google.protobuf.message_factory import MessageFactory

    def _cls(desc):
        return MessageFactory().GetPrototype(desc)

_SVC = _pb2.DESCRIPTOR.services_by_name["{service}"]


class {service}Stub:
    def __init__(self, channel):
        for m in _SVC.methods:
            setattr(
                self,
                m.name,
                channel.unary_unary(
                    "/%s/%s" % (_SVC.full_name, m.name),
                    request_serializer=_cls(m.input_type).SerializeToString,
                    response_deserializer=_cls(m.output_type).FromString,
                ),
            )


class {service}Servicer:
    pass


def _unimplemented(name):
    def method(self, request, context):
        context.set_code(grpc.StatusCode.UNIMPLEMENTED)
        context.set_details("Method %s not implemented." % name)
        raise NotImplementedError(name)

    return method


for _m in _SVC.methods:
    setattr({service}Servicer, _m.name, _unimplemented(_m.name))


def add_{service}Servicer_to_server(servicer, server):
    handlers = {{
        m.name: grpc.unary_unary_rpc_method_handler(
            getattr(servicer, m.name),
            request_deserializer=_cls(m.input_type).FromString,
            response_serializer=_cls(m.output_type).SerializeToString,
        )
        for m in _SVC.methods
    }}
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(_SVC.full_name, handlers),)
    )
'''

svc_dir = DST / 'vizier/_src/service'
for pb2, service in (
    ('vizier_service_pb2', 'VizierService'),
    ('pythia_service_pb2', 'PythiaService'),
):
    (svc_dir / f'{pb2}_grpc.py').write_text(
        _SHIM.format(pb2=pb2, service=service)
    )

# sqlalchemy is absent from this image; the servicer only touches it when
# a SQL database_url is passed. Stub the import so database_url=None (RAM
# datastore) works — that is the config the reference's own performance
# test uses in-memory equivalently.
for rel, imports in (
    ('vizier_service.py', ('import sqlalchemy as sqla',
                           'from vizier._src.service import sql_datastore')),
    ('sql_datastore.py', ('import sqlalchemy as sqla',)),
):
    p = svc_dir / rel
    src = p.read_text()
    for old in imports:
        if old in src and f'try:\n  {old}' not in src:
            name = old.rsplit(' ', 1)[-1]
            src = src.replace(
                old,
                f'try:\n  {old}\n'
                'except ModuleNotFoundError:  # absent image dep; RAM datastore only\n'
                f'  {name} = None',
            )
    p.write_text(src)

p = DST / 'vizier/pyvizier/converters/__init__.py'
src = p.read_text()
if 'ModuleNotFoundError' not in src:
    out = []
    for line in src.splitlines():
        gated = any(
            m in line
            for m in ('jnp_converters', 'padding', 'feature_mapper', 'embedder', 'spatio')
        )
        if gated and line.startswith('from'):
            out.append(
                f"try:\n    {line}\nexcept ModuleNotFoundError:"
                "  # equinox/tfp absent in this image\n    pass"
            )
        else:
            out.append(line)
    p.write_text('\n'.join(out) + '\n')
print(f'reference copy ready at {DST}')
EOF
