#!/usr/bin/env python
"""Where do the delta rule's float32 errors come from on the chip?

    chiprun -- python tools/gated_delta_error_diag.py [channel]

With ``channel``: the decay a vector a key channel (Kimi Delta Attention)
at three heads of 128 x 128, slow, middle and fast channels inside every
head: the chunked op (blocks of 16 inside a chunk), the fused Pallas scan, both
steps and the token-by-token float32 recurrence with the decay as
``exp(g)`` and as ``1 + expm1(g)``, each against float64 (PERF.md section
6, PR 43); the per-term readings are the scalar form's alone.

Each term of ``chunk_terms``, the scan, the fused Pallas scan, the step kernel
and a token-by-token float32 recurrence (what the benchmark's plain
reference runs), each against float64 numpy on the host, at 1024 tokens
and three heads of 96 x 192 with slow, middle and fast decay; then the
device's elementwise functions and one matmul against float64.  Prints a
line a reading (PERF.md section 6, PR 41, has the v5e's).  Runs on a TPU
backend only (the kernels are not interpreted here)."""
import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np, jax, jax.numpy as jnp
from paddle_tpu.ops import gated_delta_ops as G
from paddle_tpu.ops.pallas import gated_delta as K
print(jax.devices())
CHANNEL="channel" in sys.argv[1:]
B,T,H,Dk,Dv,C=(1,1024,3,128,128,64) if CHANNEL else (1,1024,3,96,192,64)
r=np.random.default_rng(0)
unit=lambda x: x/np.sqrt((x*x).sum(-1,keepdims=True)+1e-6)
q=unit(np.maximum(r.normal(size=(B,T,H,Dk)),-0.3))*Dk**-0.5; k=unit(np.maximum(r.normal(size=(B,T,H,Dk)),-0.3))
v=r.normal(size=(B,T,H,Dv))
A=np.array([0.05,3.0,12.0]); dt=np.array([0.002,0.02,0.09]); dtb=dt+np.log(-np.expm1(-dt))
if CHANNEL:   # dt_bias a channel: every head holds slow and fast channels
    dtc=np.exp(r.uniform(np.log(1e-3),np.log(0.1),size=(H,Dk))); dtb=dtc+np.log(-np.expm1(-dtc))
    g=-A[:,None]*np.log1p(np.exp(r.normal(size=(B,T,H,Dk))+dtb))
else:
    g=-A*np.log1p(np.exp(r.normal(size=(B,T,H))+dtb))
beta=2/(1+np.exp(-r.normal(size=(B,T,H))))
f32=lambda x: jnp.asarray(x,jnp.float32)
q,k,v,g,beta=(np.asarray(f32(x),'float64') for x in (q,k,v,g,beta))   # exactly representable inputs
def rel(a,b): 
    b=np.asarray(b,'float64'); return float(np.abs(np.asarray(a,'float64')-b).max()/np.abs(b).max())
# float64 truth: recurrence
S=np.zeros((H,Dk,Dv)); O=np.zeros((T,H,Dv))
for t in range(T):
    for h in range(H):
        sd=(np.exp(g[0,t,h])[:,None] if CHANNEL else np.exp(g[0,t,h]))*S[h]; rr=v[0,t,h]-sd.T@k[0,t,h]; S[h]=sd+np.outer(k[0,t,h],beta[0,t,h]*rr); O[t,h]=S[h].T@q[0,t,h]
N=T//C
if not CHANNEL:
    lay=lambda x: np.moveaxis(x.reshape((B,N,C)+x.shape[2:]),3,1)
    ql,kl,vl,gl,bl=map(lay,(q,k,v,g,beta))
    # float64 terms
    cum=np.cumsum(gl,-1); diff=cum[...,:,None]-cum[...,None,:]; tri=np.tril(np.ones((C,C),bool))
    decay=np.where(tri,np.exp(np.where(tri,diff,0)),0)
    kk=np.einsum('...id,...jd->...ij',kl,kl); a=np.where(np.tril(tri,-1),bl[...,:,None]*decay*kk,0)
    t64=np.linalg.inv(np.eye(C)+a)
    gam=np.exp(cum)[...,None]
    w=t64@(bl[...,None]*gam*kl); u0=t64@(bl[...,None]*vl); p=decay*np.einsum('...id,...jd->...ij',ql,kl); kd=kl*np.exp(cum[...,-1:]-cum)[...,None]
    truth=dict(qg=ql*gam,w=w,u0=u0,p=p,kd=kd,gc=np.exp(cum[...,-1]))
    terms=jax.jit(G.chunk_terms)(*map(f32,(ql,kl,vl,gl,bl)))
    for name,got in zip(("qg","w","u0","p","kd","gc"),terms): print("term",name,rel(got,truth[name]))
    print("inv t: max |t|", np.abs(t64).max())
    tt=jax.jit(G._unit_lower_inverse)(f32(a)); print("term t (inverse)", rel(tt,t64))
    print("cumsum", rel(jnp.cumsum(f32(gl),-1),cum))
o,s=jax.jit(lambda *x: G.chunked(*x))(*map(f32,(q,k,v,g,beta))); print("chunked xla: o",rel(o[0],O),"s",rel(s[0],S))
o,s=K.chunk(*map(f32,(q,k,v,g,beta))); print("chunked pallas (the fused kernel): o",rel(o[0],O),"s",rel(s[0],S))
if not CHANNEL:
    # scan with float64-exact terms rounded to f32: isolates the carry
    o2,s2=jax.jit(G.scan_chunks)(tuple(f32(truth[n]) for n in ("qg","w","u0","p","kd","gc")), jnp.zeros((B,H,Dk,Dv),jnp.float32))
    print("scan on exact terms: o", rel(np.moveaxis(np.asarray(o2),1,3).reshape(B,T,H,Dv)[0],O), "s", rel(s2[0],S))
def recur(q,k,v,g,beta,alpha=jnp.exp):
    def f(s,x):
        q,k,v,g,b=x
        s=(alpha(g)[:,:,None] if CHANNEL else alpha(g)[:,None,None])*s; rr=v-jnp.einsum('hkv,hk->hv',s,k); s=s+k[:,:,None]*(b[:,None]*rr)[:,None,:]
        return s, jnp.einsum('hkv,hk->hv',s,q)
    return jax.lax.scan(f,jnp.zeros((H,Dk,Dv),jnp.float32),(q,k,v,g,beta))
with jax.default_matmul_precision("highest"):
    s3,o3=jax.jit(recur)(*(f32(x[0]) for x in (q,k,v,g,beta)))
print("reference recurrence f32 highest: o",rel(o3,O),"s",rel(s3,S))
with jax.default_matmul_precision("highest"):
    s3,o3=jax.jit(lambda *x: recur(*x,alpha=lambda z: 1+jnp.expm1(z)))(*(f32(x[0]) for x in (q,k,v,g,beta)))
print("reference recurrence f32 highest, decay 1+expm1(g): o",rel(o3,O),"s",rel(s3,S))
s3,o3=jax.jit(recur)(*(f32(x[0]) for x in (q,k,v,g,beta)))
print("recurrence f32 DEFAULT precision: o",rel(o3,O),"s",rel(s3,S))
# step kernel from the true state at T-1
S1=np.zeros((H,Dk,Dv))
for t in range(T-1):
    for h in range(H):
        sd=(np.exp(g[0,t,h])[:,None] if CHANNEL else np.exp(g[0,t,h]))*S1[h]; rr=v[0,t,h]-sd.T@k[0,t,h]; S1[h]=sd+np.outer(k[0,t,h],beta[0,t,h]*rr)
st=np.stack([S1,S1]); lv=jnp.ones((1,),jnp.int32)
oo,ss=K.step(f32(q[:,-1]),f32(k[:,-1]),f32(v[:,-1]),f32(g[:,-1]),f32(beta[:,-1]),f32(st),lv)
print("step kernel: o",rel(oo[0],O[-1]),"s",rel(ss[0],S))
oo,ss=jax.jit(G.step)(f32(q[:,-1]),f32(k[:,-1]),f32(v[:,-1]),f32(g[:,-1]),f32(beta[:,-1]),f32(st),lv.astype(bool))
print("step xla: o",rel(oo[0],O[-1]),"s",rel(ss[0],S))
# elementwise functions on the device against float64
x=np.linspace(-12,4,200001)
x32=np.asarray(f32(x),'float64')
for name,fn,ref in (("exp",jnp.exp,np.exp),("softplus",jax.nn.softplus,lambda z: np.logaddexp(z,0)),
                    ("sigmoid",jax.nn.sigmoid,lambda z: 1/(1+np.exp(-z))),("silu",jax.nn.silu,lambda z: z/(1+np.exp(-z))),
                    ("log1p(exp)",lambda z: jnp.log1p(jnp.exp(z)),lambda z: np.log1p(np.exp(z))),
                    ("1+expm1",lambda z: 1+jnp.expm1(z),np.exp)):
    got=np.asarray(jax.jit(fn)(f32(x32)),'float64'); want=ref(x32)
    print("fn",name,"max rel err",float(np.max(np.abs(got-want)/np.maximum(np.abs(want),1e-30))))
y=np.abs(r.normal(size=100000))+1e-3; y32=np.asarray(f32(y),'float64')
print("fn rsqrt", float(np.max(np.abs(np.asarray(jax.jit(jax.lax.rsqrt)(f32(y32)),'float64')*np.sqrt(y32)-1))))
print("fn divide", float(np.max(np.abs(np.asarray(jax.jit(lambda a: 1.0/a)(f32(y32)),'float64')*y32-1))))
m1=r.normal(size=(512,3840)); m2=r.normal(size=(3840,60)); a1,a2=(np.asarray(f32(z),'float64') for z in (m1,m2))
print("matmul highest [512,3840]x[3840,60]", rel(jnp.dot(f32(a1),f32(a2),precision="highest"),a1@a2))
print("matmul default", rel(jnp.dot(f32(a1),f32(a2)),a1@a2))
