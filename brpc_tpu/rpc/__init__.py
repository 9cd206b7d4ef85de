"""RPC core: Channel/Controller/Server + cluster features (SURVEY.md §2.6)."""

from brpc_tpu.rpc import errno_codes
from brpc_tpu.rpc import rpc_dump as _rpc_dump  # registers rpc_dump_* flags
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.channel import Channel, ChannelOptions
from brpc_tpu.rpc.server import Server, ServerOptions
from brpc_tpu.rpc.service import Method, Service, service_from_object
from brpc_tpu.rpc.cluster_channel import ClusterChannel
from brpc_tpu.rpc.combo_channels import (
    CallMapper, ParallelChannel, PartitionChannel, PartitionParser,
    ResponseMerger, RowScatterMapper, SelectiveChannel, SubCall, SumMerger,
)
from brpc_tpu.rpc.load_balancer import LoadBalancer, new_load_balancer
from brpc_tpu.rpc.naming import NamingService, NamingServiceThread, register_naming_service
from brpc_tpu.rpc.combo_channels import DynamicPartitionChannel
from brpc_tpu.rpc.periodic_task import PeriodicTask
from brpc_tpu.rpc.progressive import ProgressiveAttachment
from brpc_tpu.rpc.data_pool import SimpleDataPool
from brpc_tpu.rpc.auth import (
    AuthContext, AuthError, Authenticator, InterceptorError,
    TokenAuthenticator,
)

__all__ = [
    "errno_codes", "Controller", "Channel", "ChannelOptions",
    "Server", "ServerOptions", "Method", "Service", "service_from_object",
    "ClusterChannel", "CallMapper", "ParallelChannel", "PartitionChannel",
    "PartitionParser", "ResponseMerger", "RowScatterMapper",
    "SelectiveChannel", "SubCall", "SumMerger",
    "LoadBalancer", "new_load_balancer",
    "NamingService", "NamingServiceThread", "register_naming_service",
    "AuthContext", "AuthError", "Authenticator", "InterceptorError",
    "TokenAuthenticator", "DynamicPartitionChannel", "PeriodicTask",
    "ProgressiveAttachment", "SimpleDataPool",
]
